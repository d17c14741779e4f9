"""Sensor-timesteps trained per second: machines x real rows x tags, counted
once per epoch (NOT x lookback), over ALL the window's calls, divided by ALL
the window's elapsed time; every call ended in ``block_until_ready``."""


def read(ctx):
    window = ctx["window"]
    return window["timesteps"] / window["elapsed_s"]
