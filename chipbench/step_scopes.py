"""
Device milliseconds an epoch under one part of an LSTM time step: the scopes
``gordo_tpu/models/specs.py`` names inside ``lstm_time_scan``'s two loops
(``lstm.fwd.gates``, ``lstm.fwd.cell``, ``lstm.bwd.read``, ``lstm.bwd.cell``,
``lstm.bwd.products``). They lie inside the ``scan`` scope, under the
entries ``scan.forward`` and ``scan.backward`` of ``chipbench/scopes.json``,
so they are read from the op paths themselves
(``scope_reduce.for_run(ctx)["by_path"]``): the self time of every path that
holds the scope's fragment, over the traced epochs. A fusion carries the path
of the op XLA names it by, so it counts whole under that op's scope.
"""

import re
import sys

from chipbench import scope_reduce

_LAYER = re.compile(r"FusedLSTMLayer_\d+")


def ms_per_epoch(ctx, fragment):
    """Milliseconds an epoch under the paths holding ``fragment``; the same
    sum for each ``FusedLSTMLayer_k`` goes to stderr. None where no path
    holds it."""
    result = scope_reduce.for_run(ctx)
    if result is None:
        return None
    paths = {p: s for p, s in result["by_path"].items() if fragment in p}
    if not paths:
        return None
    epochs = scope_reduce.traced_epochs(ctx)
    by_layer = {}
    for path, seconds in paths.items():
        match = _LAYER.search(path)
        layer = match.group(0) if match else "no layer"
        by_layer[layer] = by_layer.get(layer, 0.0) + seconds
    for layer, seconds in sorted(by_layer.items()):
        print(
            f"step_scopes: {fragment:<20} {layer:<17} "
            f"{1000.0 * seconds / epochs:.3f} ms an epoch",
            file=sys.stderr, flush=True,
        )
    return 1000.0 * sum(paths.values()) / epochs
