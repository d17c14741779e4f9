"""
Names of the program's parameter tree for the fused-LSTM autoencoder
(``gordo_tpu.models.specs.LSTMNet`` with ``fused=True``), so that parameters
the benchmark makes from the seed can be handed to ``FleetTrainer.fit`` and
what it returns can be read back under the reference's names. A pure
renaming: no array is changed, and a leading machine axis passes through.
"""


def to_program(flat, shapes):
    tree = {"Dense_0": {"kernel": flat["head.w"], "bias": flat["head.b"]}}
    for k in range(len(shapes["layer_dims"])):
        tree[f"FusedLSTMLayer_{k}"] = {
            "input_proj": {"kernel": flat[f"l{k}.wx"]},
            "recurrent_kernel": flat[f"l{k}.wh"],
            "recurrent_bias": flat[f"l{k}.b"],
        }
    return {"params": tree}


def from_program(tree, shapes):
    tree = tree["params"]
    flat = {"head.w": tree["Dense_0"]["kernel"], "head.b": tree["Dense_0"]["bias"]}
    for k in range(len(shapes["layer_dims"])):
        layer = tree[f"FusedLSTMLayer_{k}"]
        flat[f"l{k}.wx"] = layer["input_proj"]["kernel"]
        flat[f"l{k}.wh"] = layer["recurrent_kernel"]
        flat[f"l{k}.b"] = layer["recurrent_bias"]
    return flat
