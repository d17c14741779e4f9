"""
Names of the program's parameter tree for the dense autoencoder
(``gordo_tpu.models.specs.FeedForwardNet``): ``Dense_<k>`` with ``kernel``
and ``bias``, the last one the output layer. A pure renaming, as in
``adapters/lstm_ae.py``.
"""


def _n_dense(shapes):
    return len(shapes["layer_dims"]) + 1


def to_program(flat, shapes):
    return {"params": {
        f"Dense_{k}": {"kernel": flat[f"d{k}.w"], "bias": flat[f"d{k}.b"]}
        for k in range(_n_dense(shapes))
    }}


def from_program(tree, shapes):
    flat = {}
    for k in range(_n_dense(shapes)):
        flat[f"d{k}.w"] = tree["params"][f"Dense_{k}"]["kernel"]
        flat[f"d{k}.b"] = tree["params"][f"Dense_{k}"]["bias"]
    return flat
