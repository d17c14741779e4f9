"""
Pipelined host->device transfer — double-buffering for the builder's
per-bucket data path and the streaming plane's window updates
(docs/performance.md "Mixed precision, buffer donation, and transfer
pipelining").

JAX dispatch is asynchronous, but a transfer only overlaps compute if
it is ISSUED before the compute that hides it. The helpers here make
that issue-order explicit: :func:`prefetch_iter` walks a sequence of
host arrays keeping up to ``depth`` device transfers in flight ahead of
the consumer, and :func:`device_put_sliced` splits one large stacked
array into pipelined slices so the later slices transfer while the
first is already feeding the device. ``prefetch_depth=0`` (the
default) is a strict no-op: every call collapses to the exact
``jnp.asarray`` the previous code performed, pinned bit-identical by
tests/test_precision.py.

The knob is ``--prefetch-depth`` / ``GORDO_PREFETCH_DEPTH`` (knob
registry: ``prefetch_depth``); the streaming plane, which has no CLI,
reads the env var at session-apply time.
"""

import os
import typing

import numpy as np

from gordo_tpu.observability import get_registry

__all__ = [
    "env_donate",
    "env_prefetch_depth",
    "count_transfer",
    "prefetch_iter",
    "device_put_sliced",
]

#: hard ceiling on in-flight prefetched transfers — past a handful the
#: host queue depth only adds memory pressure, never overlap
MAX_PREFETCH_DEPTH = 8


def env_prefetch_depth(default: int = 0) -> int:
    """``GORDO_PREFETCH_DEPTH`` (knob ``prefetch_depth``) for planes
    with no CLI flag of their own (streaming sessions)."""
    raw = os.environ.get("GORDO_PREFETCH_DEPTH")
    if raw is None or not str(raw).strip():
        return int(default)
    try:
        depth = int(str(raw).strip())
    except ValueError:
        return int(default)
    return max(0, min(MAX_PREFETCH_DEPTH, depth))


def env_donate(default: bool = False) -> bool:
    """``GORDO_DONATE`` (knob ``donate``): donate serving-dispatch
    input buffers to XLA (the stacked batch rows) so it can reuse
    their memory for the output. Default OFF: the alias annotation
    alone changes XLA's fusion decisions — measured ~1-2 ulp output
    drift on CPU even though the donation itself is declined there —
    and the serving default is pinned bit-identical. Set to ``1`` on
    TPU serving, where the HBM reuse is the point and ulp-level drift
    is within the anomaly statistic's tolerance."""
    raw = os.environ.get("GORDO_DONATE")
    if raw is None or not str(raw).strip():
        return bool(default)
    return str(raw).strip().lower() not in ("0", "false", "no", "off")


def count_transfer(plane: str, mode: str, n: int = 1) -> None:
    """Count host->device transfers by plane (build/train/stream) and
    mode (``prefetched`` = issued ahead of the consuming dispatch,
    ``direct`` = issued on the critical path). The transfer-overlap
    ratio prefetched/(prefetched+direct) is the judging signal for the
    ``prefetch_depth`` knob."""
    if n <= 0:
        return
    get_registry().counter(
        "gordo_transfer_chunks_total",
        "Host->device transfers by plane and issue mode (prefetched "
        "vs direct); overlap ratio = prefetched / total",
        ("plane", "mode"),
    ).inc(n, plane=plane, mode=mode)


def prefetch_iter(
    items: typing.Iterable,
    depth: int = 1,
    plane: str = "train",
    put: typing.Optional[typing.Callable] = None,
):
    """
    Yield ``put(item)`` for each item, keeping up to ``depth`` results
    in flight ahead of the consumer — transfer k+1 is issued before the
    consumer finishes with transfer k, so it rides under the dispatch
    that consumes k. ``depth=0`` degrades to a plain map (every
    transfer on the critical path). ``put`` defaults to
    ``jax.device_put``.
    """
    depth = max(0, min(MAX_PREFETCH_DEPTH, int(depth)))
    if put is None:
        import jax

        put = jax.device_put
    if depth == 0:
        for item in items:
            count_transfer(plane, "direct")
            yield put(item)
        return
    import collections

    pending: typing.Deque = collections.deque()
    it = iter(items)
    try:
        while len(pending) <= depth:
            pending.append(put(next(it)))
            count_transfer(plane, "prefetched")
    except StopIteration:
        it = None
    while pending:
        out = pending.popleft()
        if it is not None:
            try:
                pending.append(put(next(it)))
                count_transfer(plane, "prefetched")
            except StopIteration:
                it = None
        yield out


def device_put_sliced(array: np.ndarray, depth: int, plane: str = "build"):
    """
    Transfer one large host array as ``depth + 1`` pipelined slices
    along axis 0, concatenated back on device. With ``depth=0`` this is
    exactly ``jnp.asarray(array)`` (bit-identical default); with
    ``depth>0`` the later slices stream while the first is already
    device-resident, overlapping transfer with the compute the caller
    launches next. Values are identical either way — slicing and
    concatenation move bytes, not math.
    """
    import jax
    import jax.numpy as jnp

    depth = max(0, min(MAX_PREFETCH_DEPTH, int(depth)))
    if depth == 0 or getattr(array, "ndim", 0) < 1 or len(array) <= depth:
        count_transfer(plane, "direct")
        return jnp.asarray(array)
    parts = np.array_split(np.asarray(array), depth + 1, axis=0)
    devs = [jax.device_put(p) for p in parts]
    count_transfer(plane, "prefetched", n=len(devs))
    return jnp.concatenate(devs, axis=0)
