"""
Fleet serving: stacked-parameter batched scoring (SURVEY.md §2.10(c)).

The reference serves one model per request (gordo/server/views/base.py) —
each POST runs one Keras forward. Here, trained same-architecture
estimators are re-stacked on a leading machine axis (the inverse of the
fleet *training* stack, gordo_tpu/parallel/fleet.py) so one jitted,
``vmap``-ed program scores a whole group of machines per dispatch: params
stay TPU-resident between requests, the machine axis rides the MXU's batch
dimension, and one compile serves every machine in the group.

Host/device split: per-machine sklearn prefix transforms (scalers) stay on
host — they're cheap and heterogeneous; the batched device program is the
model forward, where the FLOPs are.
"""

import hashlib
import json
import logging
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu.models.core import BaseJaxEstimator, _batch_bucket
from gordo_tpu.observability import attribution, emit_event, get_registry, tracing
from gordo_tpu.parallel import transfer
from gordo_tpu.parallel.precision import cast_params
from gordo_tpu.programs import ProgramCache, serving_program_cache

logger = logging.getLogger(__name__)

#: memory addresses inside reprs (bound methods, lambdas) — stripped
#: before hashing so a program identity is stable across processes
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")

#: floor on the per-dispatch machine-axis chunk for coalesced requests
#: (predict_requests): small groups still coalesce up to this many
#: entries per dispatch (64 rows of a small model's params are cheap),
#: while large groups chunk at their own resident-stack size — either
#: way the gathered-param copy stays O(group), not O(batch)
_MIN_DISPATCH_ENTRIES = 64


def _pow2_bucket(n: int, cap: Optional[int] = None) -> int:
    """Smallest power of two >= n (jit shape bucketing, <=2x padding)."""
    return _batch_bucket(n, cap, base=2)


def _group_key(est: BaseJaxEstimator) -> Tuple:
    """Machines whose estimators share this key can be stacked and vmapped.

    Per-machine inference precision (``est.precision_``, stamped by the
    builder's calibration pass — docs/performance.md "Mixed precision")
    joins the key only when non-default, mirroring
    :meth:`ProgramKey.digest_payload
    <gordo_tpu.parallel.bucketing.ProgramKey.digest_payload>`: an
    all-float32 fleet produces byte-identical keys (and so handle/AOT
    identities) to every pre-precision build, and a calibration-fallback
    machine splits into its own float32 group rather than silently
    sharing a bf16 program.
    """
    spec = est.spec_
    key = (
        repr(spec.module),
        spec.windowed,
        spec.lookback_window if spec.windowed else 1,
        est.lookahead if spec.windowed else 0,
        est.n_features_,
        est.n_features_out_,
    )
    precision = getattr(est, "precision_", "float32")
    if precision != "float32":
        key = key + (f"precision={precision}",)
    return key


def _fn_digest(key: Tuple) -> str:
    """
    Cross-process identity of a group's scoring FUNCTION (module
    architecture + window geometry + feature widths): the build-time AOT
    export and the serving process must derive the same digest from the
    same artifacts, so the module repr is canonicalized (addresses
    stripped) before hashing.
    """
    canonical = [_ADDR_RE.sub("0x0", key[0])] + [str(part) for part in key[1:]]
    return hashlib.sha1(json.dumps(canonical).encode()).hexdigest()[:16]


def _params_digest(stacked: Any) -> str:
    """Per-machine param structure digest (leaf paths + shapes MINUS the
    leading machine axis + dtypes): the machine axis is the dispatch's
    ``m`` and varies per program, so it stays out of the identity."""
    leaves = [
        (jax.tree_util.keystr(path), tuple(leaf.shape[1:]), str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_leaves_with_path(stacked)
    ]
    return hashlib.sha1(json.dumps(leaves, sort_keys=True).encode()).hexdigest()[:16]


class FleetScorer:
    """
    Batched scorer over a set of *trained* estimators.

    Estimators are grouped by architecture (module structure + window
    geometry + feature widths); each group's param pytrees are stacked on a
    leading machine axis and applied via one jitted ``vmap`` program.

    Compiled programs route through the process-wide serving
    :class:`~gordo_tpu.programs.ProgramCache` — never an ad-hoc per-group
    jit cache: the jit HANDLE is shared across scorer rebuilds of the
    same architecture (a revision roll with unchanged architecture pays
    no recompile), and when ``store`` names a build-time AOT
    :class:`~gordo_tpu.programs.ProgramStore`, exact-shape serialized
    executables are preferred over a fresh trace (docs/performance.md
    "AOT executable cache"). Every store/executable failure degrades to
    the traced path — a scorer never errors because a cache did.
    """

    def __init__(
        self,
        estimators: Dict[str, BaseJaxEstimator],
        store=None,
        cache: Optional[ProgramCache] = None,
    ):
        for name, est in estimators.items():
            if not hasattr(est, "params_"):
                raise ValueError(f"Estimator for {name!r} is not fitted")
        self._store = store
        self._cache = cache if cache is not None else serving_program_cache()
        self._groups: List[dict] = []
        by_key: Dict[Tuple, List[str]] = {}
        for name, est in estimators.items():
            by_key.setdefault(_group_key(est), []).append(name)
        donate = transfer.env_donate()
        for key, names in by_key.items():
            group_ests = [estimators[n] for n in names]
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *[e.params_ for e in group_ests]
            )
            spec = group_ests[0].spec_
            precision = getattr(group_ests[0], "precision_", "float32")
            if precision == "bf16":
                # the resident stack lives at the serving precision; the
                # batch stays float32 on the wire and is cast IN-program
                # (below), and outputs upcast IN-program — responses and
                # the anomaly statistic keep their historical dtypes
                stacked = cast_params(stacked, jnp.bfloat16)
            fn_digest = _fn_digest(key)
            if spec.windowed:
                # windows are gathered IN the compiled program from raw
                # (rows, f) inputs: the host->device transfer carries each
                # row once instead of lookback times (the gather is HBM
                # traffic, where it belongs)
                lb = spec.lookback_window
                la = group_ests[0].lookahead

                if precision == "bf16":

                    def one(p, x, module=spec.module, lb=lb, la=la):
                        starts = jnp.arange(
                            x.shape[0] - lb + 1 - la, dtype=jnp.int32
                        )
                        rows = starts[:, None] + jnp.arange(lb, dtype=jnp.int32)
                        out = module.apply(p, x[rows].astype(jnp.bfloat16))[0]
                        return out.astype(jnp.float32)

                else:

                    def one(p, x, module=spec.module, lb=lb, la=la):
                        starts = jnp.arange(
                            x.shape[0] - lb + 1 - la, dtype=jnp.int32
                        )
                        rows = starts[:, None] + jnp.arange(lb, dtype=jnp.int32)
                        return module.apply(p, x[rows])[0]

                fn = one
            elif precision == "bf16":

                def fn(p, x, module=spec.module):
                    return module.apply(p, x.astype(jnp.bfloat16))[0].astype(
                        jnp.float32
                    )

            else:

                def fn(p, x, module=spec.module):
                    return module.apply(p, x)[0]

            # the handle key is the RAW group key (repr unstripped):
            # within a process, two modules share a handle only if
            # they'd have grouped together anyway — the stripped
            # fn_digest is for CROSS-process AOT identity only
            apply_fn = self._cache.get_or_build(
                ("scorer_jit", key),
                lambda fn=fn: jax.jit(jax.vmap(fn)),
            )
            # donating twin for the TRACED dispatch path only: the batch
            # argument is always a buffer the caller never reads again
            # (fresh jnp.asarray / stack / scatter result), so XLA may
            # reuse its memory for the output. AOT exports lower from the
            # NON-donating handle — a serialized executable must be
            # replayable after an execute failure, and donation on a
            # failed exe would leave the fallback reading a dead buffer.
            apply_donate = (
                self._cache.get_or_build(
                    ("scorer_jit_donate", key),
                    lambda fn=fn: jax.jit(jax.vmap(fn), donate_argnums=(1,)),
                )
                if donate
                else None
            )
            self._groups.append(
                {
                    "names": names,
                    "params": stacked,
                    "apply": apply_fn,
                    "apply_donate": apply_donate,
                    "precision": precision,
                    "fn_digest": fn_digest,
                    "params_digest": _params_digest(stacked),
                    "aot_ok": True,
                    "windowed": spec.windowed,
                    "lookback": spec.lookback_window if spec.windowed else 1,
                    "lookahead": group_ests[0].lookahead if spec.windowed else 0,
                    "n_features": group_ests[0].n_features_,
                    "n_features_out": group_ests[0].n_features_out_,
                    # per-machine REAL widths (padded-bucket artifacts —
                    # docs/serving.md "Padded programs"): inputs pad up
                    # to the program width before dispatch, outputs strip
                    # back down before the response. Exact artifacts
                    # record their program widths here, making both a
                    # no-op.
                    "in_cols": {
                        n: getattr(e, "n_active_features_", None)
                        or e.n_features_
                        for n, e in zip(names, group_ests)
                    },
                    "out_cols": {
                        n: getattr(e, "n_active_features_out_", None)
                        or e.n_features_out_
                        for n, e in zip(names, group_ests)
                    },
                }
            )
        # digest-collision guard: two DISTINCT groups whose identities
        # collapse to the same (fn, params) digest — possible only when
        # their module reprs differ solely inside stripped 0x… address
        # tokens (e.g. two different lambdas) — would share one stored
        # executable and silently serve each other's program. Disable
        # AOT for the colliding groups (export skips them, dispatch
        # never loads for them); the jitted path serves them correctly.
        by_identity: Dict[Tuple[str, str], List[dict]] = {}
        for group in self._groups:
            by_identity.setdefault(
                (group["fn_digest"], group["params_digest"]), []
            ).append(group)
        for identity, colliding in by_identity.items():
            if len(colliding) > 1:
                logger.warning(
                    "AOT disabled for %d scorer groups sharing program "
                    "identity %s (address-stripped repr collision); they "
                    "will trace instead",
                    len(colliding), identity,
                )
                for group in colliding:
                    group["aot_ok"] = False

    @property
    def names(self) -> List[str]:
        return [n for g in self._groups for n in g["names"]]

    @property
    def n_groups(self) -> int:
        return len(self._groups)

    def machine_geometry(self, name: str) -> Dict[str, Any]:
        """One machine's dispatch geometry — what the streaming session
        layer needs to size its device-resident window and validate
        update widths (docs/serving.md "Streaming scoring")."""
        for group in self._groups:
            if name in group["names"]:
                return {
                    "windowed": group["windowed"],
                    "lookback": group["lookback"],
                    "lookahead": group["lookahead"],
                    "n_features": group["in_cols"][name],
                    "n_features_out": group["out_cols"][name],
                }
        raise KeyError(f"No stacked params for machine {name!r}")

    def _aot_targets(
        self, row_buckets: Sequence[int]
    ) -> List[Tuple[dict, int, int]]:
        """(group, m, rows) for every program worth shipping: the
        resident full-group machine axis (floored at 2 — single-machine
        groups dispatch through the >=2-padded gather path on every
        request, fleet_serving's bit-identity floor), × each row bucket
        a request can pad into (windowed groups skip buckets too short
        for one window — the per-model path's own error case)."""
        targets = []
        for group in self._groups:
            if not group["aot_ok"]:
                continue
            m = max(2, len(group["names"]))
            for rows in sorted(set(int(r) for r in row_buckets)):
                if (
                    group["windowed"]
                    and rows - group["lookback"] + 1 - group["lookahead"] <= 0
                ):
                    continue
                targets.append((group, m, rows))
        return targets

    def export_programs(
        self, store, row_buckets: Optional[Sequence[int]] = None
    ) -> List[dict]:
        """
        Build-time AOT: lower + compile each serving program at its
        exact dispatch shapes and serialize into ``store``
        (docs/performance.md "AOT executable cache"). Returns the
        exported shape keys; the caller owns writing the manifest's
        sibling artifacts. Best-effort per program: one architecture
        failing to serialize skips that program, never the build.
        """
        from gordo_tpu.programs.aot import fresh_compile, serving_row_buckets

        if row_buckets is None:
            row_buckets = serving_row_buckets()
        exported: List[dict] = []
        for group, m, rows in self._aot_targets(row_buckets):
            key = self._aot_key(group, m, rows)
            params_struct = jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(
                    (m,) + leaf.shape[1:], leaf.dtype
                ),
                group["params"],
            )
            batch_struct = jax.ShapeDtypeStruct(
                (m, rows, group["n_features"]), jnp.float32
            )
            try:
                with tracing.start_span(
                    "program.compile", m=m, rows=rows, fn=group["fn_digest"]
                ), fresh_compile():
                    compiled = group["apply"].lower(
                        params_struct, batch_struct
                    ).compile()
                store.save(key, compiled)
            except Exception as exc:  # noqa: BLE001 - export is best-effort
                logger.warning(
                    "AOT export skipped for %s (m=%d rows=%d): %s",
                    group["fn_digest"], m, rows, exc,
                )
                continue
            exported.append(key)
        store.write_manifest()
        emit_event(
            "program_cache_export",
            n_programs=len(exported),
            output_dir=str(store.directory),
        )
        return exported

    def warm_from_store(self) -> int:
        """
        Eagerly deserialize every stored executable matching this
        scorer's groups (the preload path: pay the loads behind the
        readiness probe, not the first request). Returns programs now
        resident; load failures fall back silently per program.
        """
        if self._store is None:
            return 0
        # identity AND dispatch-shape match: a store built for a larger
        # stack of the same architecture (machine axis m differs) holds
        # programs this scorer can never dispatch — loading them would
        # only burn memory
        identities = {
            (g["fn_digest"], g["params_digest"], max(2, len(g["names"])))
            for g in self._groups
            if g["aot_ok"]
        }
        loaded = 0
        for key in self._store.keys():
            if key.get("kind") != "fleet_scorer":
                continue
            identity = (key.get("fn"), key.get("params"), key.get("m"))
            if identity not in identities:
                continue
            if self._cache.aot_program(key, self._store) is not None:
                loaded += 1
        return loaded

    def predict(self, inputs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """
        Model outputs for each named machine. ``inputs[name]`` is the
        machine's (already host-transformed) model input, shape
        (n_rows, n_features); rows may differ per machine — machines are
        zero-padded to the power-of-two bucket above the group's max (so
        jit sees bounded shapes) and sliced back.

        Delegates to :meth:`predict_requests` with a one-request batch:
        the solo and coalesced (dynamic-batching) paths are ONE code
        path, so batched vs. unbatched serving cannot drift.
        """
        return self.predict_requests([inputs])[0]

    def predict_requests(
        self, requests: Sequence[Dict[str, np.ndarray]]
    ) -> List[Dict[str, np.ndarray]]:
        """
        Coalesced scoring of several requests' inputs — the server's
        dynamic-batching entry point (``server/batching.py``): all
        requests' (machine, X) entries stack on the SAME leading machine
        axis a solo request uses, ONE dispatch per architecture group. A
        machine named by k requests occupies k rows (its params gathered
        with repeats — XLA's per-row results are batch-shape-invariant,
        pinned by test). Returns one ``{name: output}`` dict per request,
        in request order.
        """
        known = set(self.names)
        for inputs in requests:
            missing = set(inputs) - known
            if missing:
                raise KeyError(
                    f"No stacked params for machines: {sorted(missing)}"
                )
        out: List[Dict[str, np.ndarray]] = [{} for _ in requests]
        reg = get_registry()
        for group in self._groups:
            # per request, entries follow group order — the same order
            # the solo path has always dispatched in
            entries = [
                (ridx, name, inputs[name])
                for ridx, inputs in enumerate(requests)
                for name in group["names"]
                if name in inputs
            ]
            if not entries:
                continue
            windowed = "true" if group["windowed"] else "false"
            # bound the machine axis per dispatch: duplicate-machine
            # entries (the normal coalesced case) take the param-GATHER
            # path below, so device memory per dispatch scales with the
            # entry count — chunking at ~the resident stack's own size
            # keeps that at O(group), never O(batch). Solo requests
            # (entries <= group size) are always one chunk.
            chunk = max(_MIN_DISPATCH_ENTRIES, len(group["names"]))
            for cstart in range(0, len(entries), chunk):
                sub = entries[cstart : cstart + chunk]
                start = time.perf_counter()
                results = self._predict_entries(group, sub)
                elapsed = time.perf_counter() - start
                for (ridx, name, _), value in zip(sub, results):
                    out[ridx][name] = value
                reg.histogram(
                    "gordo_serve_group_latency_seconds",
                    "One vmapped fleet-scoring dispatch (host->device->host)",
                    ("windowed",),
                ).observe(elapsed, windowed=windowed)
                reg.histogram(
                    "gordo_serve_group_batch_size",
                    "Machines scored per fleet dispatch",
                    ("windowed",),
                    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
                ).observe(len(sub), windowed=windowed)
                reg.counter(
                    "gordo_serve_machines_scored_total",
                    "Machines scored through the fleet path",
                    ("windowed",),
                ).inc(len(sub), windowed=windowed)
        return out

    def _aot_key(self, group: dict, m: int, rows: int) -> Dict[str, Any]:
        """The cross-process shape key one compiled dispatch is stored
        under: program identity (function + per-machine param structure)
        plus this dispatch's exact (machine-axis, row-bucket) shape.

        Non-default precision is an explicit manifest field (on top of
        already splitting both digests): an executable compiled at one
        precision must never be served for another, and the store's
        manifest should say so in the open rather than only via opaque
        hashes. float32 keys are byte-identical to every pre-precision
        store, so existing AOT caches keep hitting."""
        key = {
            "kind": "fleet_scorer",
            "fn": group["fn_digest"],
            "params": group["params_digest"],
            "m": int(m),
            "rows": int(rows),
        }
        if group.get("precision", "float32") != "float32":
            key["precision"] = group["precision"]
        return key

    def _dispatch(
        self, group: dict, params: Any, batch, m: int, rows: int
    ):
        """
        One device dispatch of ``m`` machine rows × ``rows`` padded
        timesteps: an exact-shape AOT executable when the program cache
        (or attached store) has one, else the group's jitted handle —
        which traces/compiles on first use, the graceful floor every
        cache failure lands on. An executable that LOADS but fails to
        execute (shape drift, runtime error) is evicted and the request
        retraces — degraded latency, never a serving error.

        Returns the raw (device) result; the caller owns the
        device->host conversion — the streaming path fetches only its
        per-entry output slices, the one-shot path the whole array.
        """
        exe = (
            self._cache.aot_program(self._aot_key(group, m, rows), self._store)
            if group["aot_ok"]
            else None
        )
        if exe is not None:
            try:
                # dispatch is asynchronous: an executable that loaded but
                # cannot run reports it only when its result is awaited —
                # await it HERE so that failure lands on the ladder, not
                # in the caller's device->host fetch as a failed request
                return jax.block_until_ready(exe(params, jnp.asarray(batch)))
            except Exception as exc:  # noqa: BLE001 - ANY failure retraces
                logger.warning(
                    "AOT executable failed at dispatch (%s); retracing", exc
                )
                self._cache.discard_aot(
                    self._aot_key(group, m, rows), reason="execute_error"
                )
        # traced path: prefer the donating twin when GORDO_DONATE opted
        # in — the batch buffer is dispatch-local, so XLA may reuse it
        # for the output. Safe after an exe failure too: stored
        # executables never donate, so the batch is still live here.
        apply_fn = group.get("apply_donate") or group["apply"]
        return apply_fn(params, jnp.asarray(batch))

    def _predict_entries(
        self, group: dict, entries: List[Tuple[int, str, np.ndarray]]
    ) -> List[np.ndarray]:
        """One stacked dispatch for ``entries`` = [(request_idx, name,
        X), ...] of one group; returns outputs aligned with entries.

        An entry's X may be a host array (the one-shot POST path) or a
        :class:`~gordo_tpu.streaming.window.WindowUpdate` (the streaming
        path: device-resident context + freshly transferred new rows).
        Both assemble into ONE stacked batch — on host when every entry
        is host-side (the historical path, byte-identical), on device
        when any stream entry is present (padding/stacking are pure
        data movement, so the batch holds the same bits either way and
        the dispatch program cannot tell the difference; pinned by
        tests/test_streaming.py).
        """
        from gordo_tpu.streaming.window import WindowUpdate

        # phase-ledger bookmark: everything up to the dispatch is host
        # batch assembly + staging ("transfer"); the dispatch plus the
        # device->host output sync in slices() is "device"
        t_assemble = time.perf_counter()
        names = [name for _, name, _ in entries]
        lb, la = group["lookback"], group["lookahead"]
        f_prog = group["n_features"]
        prepared = []
        on_device = False
        for _, name, X in entries:
            # inputs must carry the machine's REAL width (its tag list);
            # zero-filling an arbitrary short frame up to the program
            # width would feed untrained (or wrong) input units and
            # return confident garbage — only the pad from real width to
            # program width is inert by the training-side invariant
            n_real = group["in_cols"][name]
            if isinstance(X, WindowUpdate):
                on_device = True
                if X.width != n_real:
                    raise ValueError(
                        f"Machine {name!r} expects {n_real} feature "
                        f"column(s), got {X.width}"
                    )
                x = X.materialize()  # the update's only host->device copy
                if n_real < f_prog:
                    x = jnp.pad(x, ((0, 0), (0, f_prog - n_real)))
            else:
                x = np.asarray(X, dtype=np.float32)
                if x.shape[-1] != n_real:
                    raise ValueError(
                        f"Machine {name!r} expects {n_real} feature "
                        f"column(s), got {x.shape[-1]}"
                    )
                if n_real < f_prog:
                    # padded-bucket machine: widen to the program width
                    # with inert zero columns
                    x = np.pad(
                        x, [(0, 0)] * (x.ndim - 1) + [(0, f_prog - n_real)]
                    )
            prepared.append(x)
        max_len = max(len(x) for x in prepared)
        if group["windowed"]:
            # raw rows go to the device; the compiled program gathers the
            # windows there. n_rows tracks each machine's OUTPUT length —
            # and a machine that cannot fill ONE window is the same error
            # the per-model path raises (ops.windowing), not a silent
            # empty frame
            for name, x in zip(names, prepared):
                if len(x) - lb + 1 - la <= 0:
                    raise ValueError(
                        f"Not enough timesteps ({len(x)}) for machine "
                        f"{name!r}: lookback_window={lb}, lookahead={la}"
                    )
            n_rows = [len(x) - lb + 1 - la for x in prepared]
        else:
            n_rows = [len(x) for x in prepared]
        # bucket BOTH varying axes so jit sees a bounded set of shapes:
        # rows to the next power of two (<=2x padded compute beats a
        # per-request XLA compile), machines likewise capped at group size
        max_rows = _pow2_bucket(max_len)
        if on_device:
            batch = jnp.stack(
                [jnp.pad(x, ((0, max_rows - len(x)), (0, 0))) for x in prepared]
            )
        else:
            batch = np.stack(
                [
                    np.pad(x, [(0, max_rows - len(x))] + [(0, 0)] * (x.ndim - 1))
                    for x in prepared
                ]
            )

        def slices(outputs, index_of):
            """Per-entry output views, device->host. One-shot batches
            fetch the whole array once (the historical transfer shape);
            batches carrying stream entries slice ON device first, so a
            streamed update's device->host traffic is its own outputs,
            not the padded batch."""
            if not on_device:
                outputs = np.asarray(outputs)
            return [
                np.asarray(
                    outputs[index_of(i), : n_rows[i], : group["out_cols"][name]]
                )
                for i, name in enumerate(names)
            ]

        group_size = len(group["names"])
        if len(set(names)) == len(names) and group_size >= 2:
            # floor of 2 (see the gather comment below); group_size >= 2
            # keeps the cap from undoing it
            m_bucket = min(max(2, _pow2_bucket(len(names))), group_size)
            if names == group["names"] or m_bucket == group_size:
                # full group, or a subset whose bucket rounds up to it:
                # scatter inputs into group positions (zeros for absent
                # machines) and reuse the resident stack — no param
                # leaves are copied
                params = group["params"]
                row_index = {n: i for i, n in enumerate(group["names"])}
                if on_device:
                    scatter = jnp.asarray(
                        [row_index[name] for name in names], dtype=jnp.int32
                    )
                    full = jnp.zeros(
                        (group_size,) + batch.shape[1:], dtype=batch.dtype
                    ).at[scatter].set(batch)
                else:
                    full = np.zeros(
                        (group_size,) + batch.shape[1:], dtype=batch.dtype
                    )
                    for i, name in enumerate(names):
                        full[row_index[name]] = batch[i]
                t_dispatch = time.perf_counter()
                attribution.record_current(
                    "transfer", t_dispatch - t_assemble
                )
                outputs = self._dispatch(
                    group, params, full, group_size, max_rows
                )
                result = slices(outputs, lambda i: row_index[names[i]])
                attribution.record_current(
                    "device", time.perf_counter() - t_dispatch
                )
                return result
        else:
            # coalesced requests may name one machine several times: the
            # machine axis holds one row per ENTRY, so the bucket is not
            # capped at the group size. Floor of 2: XLA compiles a
            # machine-axis-1 program with last-ulp-different results
            # than the >=2 shape family (batch-1 special case), so
            # EVERY gather dispatch — a solo single-machine request
            # included — pads to >=2 to keep batched == unbatched
            # bit-identical (pinned by tests/test_batching.py)
            m_bucket = max(2, _pow2_bucket(len(names)))
        # subset (or duplicated-entry) dispatch: gather those machines'
        # params, padded with dummy repeats to the machine bucket
        # (sliced off below)
        sel = [group["names"].index(n) for n in names]
        sel += [sel[0]] * (m_bucket - len(sel))
        if len(set(sel)) == 1:
            # single-machine groups land here on EVERY request (their
            # resident stack is axis-1, outside the >=2 shape family):
            # the repeated-row stack depends only on (bucket, machine),
            # so cache it instead of re-copying params per request —
            # the hot path stays zero-copy like the resident one
            cache = group.setdefault("_repeat_params", {})
            cache_key = (sel[0], m_bucket)
            params = cache.get(cache_key)
            if params is None:
                while len(cache) >= 128:  # bound resident copies
                    cache.pop(next(iter(cache)))
                idx = np.asarray(sel, dtype=np.int32)
                params = jax.tree_util.tree_map(
                    lambda leaf: leaf[idx], group["params"]
                )
                cache[cache_key] = params
        else:
            sel = np.asarray(sel, dtype=np.int32)
            params = jax.tree_util.tree_map(
                lambda leaf: leaf[sel], group["params"]
            )
        if len(batch) < m_bucket:
            pad_spec = [(0, m_bucket - len(batch))] + [(0, 0)] * (batch.ndim - 1)
            batch = (
                jnp.pad(batch, pad_spec) if on_device else np.pad(batch, pad_spec)
            )
        t_dispatch = time.perf_counter()
        attribution.record_current("transfer", t_dispatch - t_assemble)
        outputs = self._dispatch(group, params, batch, m_bucket, max_rows)
        result = slices(outputs, lambda i: i)
        attribution.record_current("device", time.perf_counter() - t_dispatch)
        return result


def fleet_scorer_from_models(
    models: Dict[str, Any], store=None
) -> Tuple[Optional[FleetScorer], Dict[str, List], Dict[str, Any]]:
    """
    Build a FleetScorer from full (possibly wrapped) models as the server
    loads them: returns (scorer, host prefix-transformers per machine,
    non-batchable models that must fall back to per-model predict).
    ``store`` attaches the collection's AOT program store so dispatches
    prefer build-time serialized executables over a fresh trace.
    """
    from gordo_tpu.builder.fleet_build import _find_jax_estimator, _prefix_transformers

    estimators: Dict[str, BaseJaxEstimator] = {}
    prefixes: Dict[str, List] = {}
    fallback: Dict[str, Any] = {}
    for name, model in models.items():
        est = _find_jax_estimator(model)
        if est is None or not hasattr(est, "params_"):
            fallback[name] = model
        else:
            estimators[name] = est
            prefixes[name] = _prefix_transformers(model)
    scorer = FleetScorer(estimators, store=store) if estimators else None
    return scorer, prefixes, fallback
