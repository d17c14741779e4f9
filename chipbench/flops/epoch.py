"""
Operations and compulsory bytes of one training epoch of a fleet bucket, from
the configuration's shapes and the model kind's per-sample count.

Compulsory bytes are what any schedule has to move through the chip's memory
once: every row of X, y and the sample weights read once per epoch, and the
parameters with Adam's two moments read at the start of the epoch program and
written at its end. Activations, gathered windows and per-step state traffic
are the schedule's choice and are not counted, so a roofline share from these
bytes is a floor for the time, never an excuse.
"""

import math


def samples_per_epoch(shapes, rows):
    return rows - shapes["lookback"] + 1 if shapes.get("lookback", 1) > 1 else rows


def steps_per_epoch(shapes, rows, batch_size):
    return max(1, math.ceil(samples_per_epoch(shapes, rows) / batch_size))


def epoch_flops(kind_flops, shapes, rows, machines):
    return (
        machines * samples_per_epoch(shapes, rows)
        * kind_flops.train_flops_per_sample(shapes)
    )


def epoch_bytes(kind_flops, shapes, rows, machines):
    data = rows * (shapes["n_features"] + shapes["n_features_out"] + 1) * 4
    state = kind_flops.n_params(shapes) * 4 * 3 * 2
    return machines * (data + state)


def least_seconds(flops, n_bytes, peak):
    """The roofline's least time and which bound sets it."""
    by_flops = flops / peak["flops_per_s"]
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
