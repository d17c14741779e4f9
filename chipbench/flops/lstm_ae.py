"""
Operations and compulsory bytes of the stacked-LSTM autoencoder, from its
shapes alone. Matrix products only (2 x rows x inner x cols each): the gates'
elementwise work is not counted, so a share of the peak is a little low.
Backward costs twice the forward's products, less the gradient with respect
to the first layer's input, which no one needs; nothing recomputed is counted.
"""


def forward_flops_per_sample(shapes):
    """One window of ``lookback`` rows through every layer and the head."""
    t = shapes["lookback"]
    f_in = shapes["n_features"]
    flops = 0
    for h in shapes["layer_dims"]:
        flops += t * (2 * f_in * 4 * h + 2 * h * 4 * h)
        f_in = h
    return flops + 2 * f_in * shapes["n_features_out"]


def train_flops_per_sample(shapes):
    first_input_grad = (
        shapes["lookback"] * 2 * shapes["n_features"] * 4 * shapes["layer_dims"][0]
    )
    return 3 * forward_flops_per_sample(shapes) - first_input_grad


def n_params(shapes):
    f_in = shapes["n_features"]
    n = 0
    for h in shapes["layer_dims"]:
        n += f_in * 4 * h + h * 4 * h + 4 * h
        f_in = h
    return n + f_in * shapes["n_features_out"] + shapes["n_features_out"]
