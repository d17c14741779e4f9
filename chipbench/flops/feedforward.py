"""
Operations of the dense autoencoder, from its shapes alone: matrix products
only; backward twice the forward, less the gradient with respect to the first
layer's input, which no one needs; nothing recomputed counted.
"""


def _dims(shapes):
    return [shapes["n_features"], *shapes["layer_dims"], shapes["n_features_out"]]


def forward_flops_per_sample(shapes):
    dims = _dims(shapes)
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))


def train_flops_per_sample(shapes):
    dims = _dims(shapes)
    return 3 * forward_flops_per_sample(shapes) - 2 * dims[0] * dims[1]


def n_params(shapes):
    dims = _dims(shapes)
    return sum(a * b + b for a, b in zip(dims, dims[1:]))
