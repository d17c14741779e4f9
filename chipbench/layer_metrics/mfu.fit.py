"""The whole step's share of the chip's peak: forward+backward matrix-product
operations of every epoch the window completed (from the configuration's
shapes, ``chipbench/flops``), over the window's whole elapsed time, over
chips x peak. Float32 at default precision multiplies in one bf16 pass on a
TPU v5e, so the bf16 peak of ``chipbench/peaks.json`` is the divisor."""

from chipbench import loading
from chipbench.flops import epoch


def read(ctx):
    config, window = ctx["config"], ctx["window"]
    kind = loading.kind_module("flops", config["model_kind"])
    epochs_done = len(window["calls"]) * window["epochs_per_call"]
    flops = epochs_done * epoch.epoch_flops(
        kind, config["shapes"], config["bucket"]["rows"], config["bucket"]["machines"]
    )
    peak = ctx["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * flops / window["elapsed_s"] / peak
