"""QueryEngine: one serve subsystem, pluggable intersection backends.

Every query path in the repo (host point queries, batched device serving,
sharded production serving, benchmarks, examples) routes through here. The
engine owns the serving pipeline:

    queries -> prefilters (repro.serve.prefilter)
            -> length-bucketed micro-batches (repro.serve.planner)
            -> backend intersection
            -> scatter back

On the bucketed dense/kernel backends a store no wider than
``FUSED_MAX_WIDTH`` slots skips the host prefilter and the tier plan: one
fused device program per batch prefilters, gathers full-width rows and
intersects them, and returns one small code per row.

Backends:
  host         per-query sorted merge on the CPU (searchsorted + rank-ordered
               early exit; the reference path)
  dense        all-pairs jnp compare, jit per (tile, width) — the XLA path
  kernel       Pallas ``label_intersect`` (interpret off-TPU)
  sharded      labels replicated, queries sharded over the data axes
  sharded_hop  label matrices sharded over the model axis along the hop dim
               (labels-larger-than-one-device mode), OR-reduced

``backend="auto"`` picks: sharded when a mesh is supplied, kernel on TPU,
dense otherwise.
"""
from __future__ import annotations

import copy
import threading
import time
import warnings
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft import inject
from repro.graph.csr import INVALID
from repro.obs import metrics, trace
from repro.obs.stages import NO_TICK, StageFamily
from repro.serve.planner import plan_batch, tier_widths, whole_batch_plan
from repro.serve.prefilter import apply_prefilters, prefilter_pairs

BACKENDS = ("host", "dense", "kernel", "sharded", "sharded_hop")

# The fused program compares every row at the store's full padded width.  Up
# to one lane row, 128 slots, that is cheap: a 4,096-row batch is 4,096 x 128
# x 128 = 67 M compares on the device, where the host prefilter and tier plan
# it replaces cost the host more than the device's whole share of a batch.
# Wider stores keep the tier plan, which spares their short rows the compares
# of the widest width squared.
FUSED_MAX_WIDTH = 128

# the fused program's code for each row
_HIT = 1        # u reaches v
_DECIDED = 2    # the prefilter decided the row (``engine_prefiltered_total``)


def select_backend(name: Optional[str] = None, mesh=None) -> str:
    """Resolve a backend name ('auto'/None = detect from mesh + platform)."""
    if name in (None, "auto"):
        if mesh is not None:
            return "sharded"
        return "kernel" if jax.default_backend() == "tpu" else "dense"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    if name in ("sharded", "sharded_hop") and mesh is None:
        raise ValueError(f"backend {name!r} requires a mesh")
    return name


# ---------------------------------------------------------------- primitives


@jax.jit
def intersect_rows(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a: int32[B, La], b: int32[B, Lb] (INVALID padded) -> bool[B]."""
    eq = a[:, :, None] == b[:, None, :]
    valid = (a[:, :, None] != INVALID) & (b[:, None, :] != INVALID)
    return (eq & valid).any(axis=(1, 2))


@partial(jax.jit, static_argnames=("use_kernel",))
def serve_step(
    L_out: jnp.ndarray,
    L_in: jnp.ndarray,
    queries: jnp.ndarray,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """One-shot batched intersection at full label width (the legacy path;
    the engine adds prefilters + bucketing on top).

    L_out: int32[n, Lo], L_in: int32[n, Li], queries: int32[B, 2].
    """
    a = jnp.take(L_out, queries[:, 0], axis=0)
    b = jnp.take(L_in, queries[:, 1], axis=0)
    if use_kernel:
        from repro.kernels.ops import label_intersect

        return label_intersect(a, b)
    return intersect_rows(a, b)


def _gather_intersect(L_out, L_in, queries, width: Optional[int], use_kernel: bool):
    """Gather both label rows of each query, cut to ``width`` columns (None:
    the full padded width), and intersect them with the backend's compare."""
    a = jnp.take(L_out, queries[:, 0], axis=0)[:, :width]
    b = jnp.take(L_in, queries[:, 1], axis=0)[:, :width]
    if use_kernel:
        from repro.kernels.ops import label_intersect

        return label_intersect(a, b)
    return intersect_rows(a, b)


@partial(jax.jit, static_argnames=("width", "use_kernel"))
def _tier_intersect(L_out, L_in, queries, width: int, use_kernel: bool):
    """Gather + truncate to the tier width + intersect. One trace per
    (tile rows, width, backend) triple."""
    return _gather_intersect(L_out, L_in, queries, width, use_kernel)


@partial(jax.jit, static_argnames=("use_kernel",))
def _tier_intersect_fused(L_out, L_in, meta, queries, use_kernel: bool):
    """The whole batch in one program: the prefilters over each pair's row
    of ``meta`` (``_vertex_meta``), then every row gathered at the store's
    full width and intersected.  Returns uint8[B] codes, ``_HIT`` for a
    reachable pair plus ``_DECIDED`` where the prefilter decided it.  One
    trace per (tile rows, store shape, backend)."""
    u, v = queries[:, 0], queries[:, 1]
    # one row gather per side: four 1-D gathers of the lengths and levels
    # took most of the device time on a v5e
    mu, mv = jnp.take(meta, u, axis=0), jnp.take(meta, v, axis=0)
    levels = (mu[:, 2], mv[:, 2]) if meta.shape[1] > 2 else ()
    pf = prefilter_pairs(u == v, mu[:, 0], mv[:, 1], *levels)
    hit = _gather_intersect(L_out, L_in, queries, None, use_kernel)
    verdict = jnp.where(pf.decided, pf.value, hit)
    return (verdict.astype(jnp.uint8) * _HIT) | (pf.decided.astype(jnp.uint8) * _DECIDED)


# ------------------------------------------------------------ sharded modes


def make_sharded_serve_step(mesh, data_axes=("pod", "data")):
    """Production serve_step: labels replicated over the model axis, queries
    sharded over the data axes. Returns (jitted_fn, in_shardings, out_sharding).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    label_sharding = NamedSharding(mesh, P())               # replicated
    query_sharding = NamedSharding(mesh, P(data_axes, None))
    out_sharding = NamedSharding(mesh, P(data_axes))

    fn = jax.jit(
        lambda lo, li, q: serve_step(lo, li, q),
        in_shardings=(label_sharding, label_sharding, query_sharding),
        out_shardings=out_sharding,
    )
    return fn, (label_sharding, label_sharding, query_sharding), out_sharding


def make_hop_sharded_serve_step(mesh, model_axis="model", data_axes=("pod", "data")):
    """Large-graph variant: label MATRICES sharded over the model axis along
    the hop dimension (each device holds a slice of every row); each shard
    computes a partial intersection hit and the results OR-reduce over the
    model axis. Queries sharded over data axes.

    This is the "labels larger than one device" serving mode.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    label_sharding = NamedSharding(mesh, P(None, model_axis))
    query_sharding = NamedSharding(mesh, P(data_axes, None))
    out_sharding = NamedSharding(mesh, P(data_axes))

    def local(lo, li, q):
        # each hop-shard of `a` must compare against ALL hops of b: gather
        # the (small) b rows over the model axis; the big label matrices
        # stay sharded, and the partial hits OR-reduce over the model axis
        a = jnp.take(lo, q[:, 0], axis=0)
        b = jax.lax.all_gather(jnp.take(li, q[:, 1], axis=0), model_axis,
                               axis=1, tiled=True)
        hit = intersect_rows(a, b).astype(jnp.int32)
        return jax.lax.pmax(hit, model_axis) > 0

    fn = jax.jit(
        jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, model_axis), P(None, model_axis),
                                P(data_axes, None)),
                      out_specs=P(data_axes)),
        in_shardings=(label_sharding, label_sharding, query_sharding),
        out_shardings=out_sharding,
    )
    return fn, (label_sharding, label_sharding, query_sharding), out_sharding


# ----------------------------------------------------------------- engine

# every downgrade the ladder can count; stats()/reset_stats() and the
# per-batch tallies all start from this shape so no consumer ever sees a
# partially populated counter dict
_ZERO_DEGRADATION = {
    "device_to_host": 0,   # device backend failed -> host merge
    "deadline_to_host": 0, # batch past deadline -> skip device (retrace risk)
    "searched": 0,         # labels unusable -> exact bidirectional search
    "quarantined": 0,      # queries that touched quarantined label rows
    "uncertain": 0,        # budget-truncated miss, BOTH rows cut -> search
}

# registry mirrors (process-global; the per-engine ``degradation`` dict stays
# the per-instance view health()/chaos read)
_M_QUERIES = metrics.counter(
    "engine_queries_total", "queries through QueryEngine.query_batch")
_M_PREFILTERED = metrics.counter(
    "engine_prefiltered_total", "queries decided by the prefilter stack")
_M_DEGRADED = metrics.counter(
    "engine_degraded_total", "ladder downgrades, by kind", labelnames=("kind",))
_DEGRADED_KIND = {k: _M_DEGRADED.labels(kind=k) for k in _ZERO_DEGRADATION}
_M_EPOCH = metrics.gauge(
    "engine_epoch", "label-snapshot epoch the engine currently serves")
_M_DEVICE_BATCHES = metrics.counter(
    "engine_device_batches_total",
    "batches the bucketed dense/kernel backends served on the device, by path",
    labelnames=("path",))
_DEVICE_PATH = {p: _M_DEVICE_BATCHES.labels(path=p) for p in ("fused", "tiered")}
# one observation per batch of each stage that ran, its tiers summed:
#   map        original ids -> condensation ids, and the batch's set-up
#   prefilter  the prefilter stack over the batch (host; not on the fused path)
#   plan       tier plan and tile padding (host; not on the fused path)
#   enqueue    the device calls, one per tier or one fused program, with the
#              query upload (returns before the device ends)
#   sync       the blocking copy of the results to the host
#   scatter    the results back into batch order (the fused codes decoded)
_STAGES = StageFamily(
    "engine_stage_ms", "engine batch time by stage",
    ("map", "prefilter", "plan", "enqueue", "sync", "scatter"), cat="engine")


class QueryEngine:
    """The serve subsystem for one ReachabilityOracle.

    Parameters
    ----------
    oracle : ReachabilityOracle
        Labels in the engine's id space (condensation ids when built through
        ``repro.core.api``).
    backend : str
        One of BACKENDS or "auto".
    level : optional int32[n]
        Topological levels for the level prefilter (``prefilter.topo_levels``).
    mesh : optional jax Mesh
        Required for the sharded backends.
    bucketing : bool
        Length-bucketed micro-batching for dense/kernel backends.
    comp_source : optional callable -> int32[n_original]
        When set, queries arrive in ORIGINAL vertex ids and are mapped to the
        oracle's condensation id space through ``comp_source()`` at call time.
        The indirection is deliberate: the owner (``CondensedOracle`` /
        ``repro.dynamic.DynamicOracle``) controls which comp array is current,
        so SCC-condensation merges can never serve a stale same-SCC verdict
        from a comp array cached inside the engine.
    epoch : int
        Label-snapshot epoch this engine currently serves (see
        ``repro.dynamic.versioned``); bumped by ``refresh``.
    fallback_graph : optional CSRGraph or callable -> CSRGraph
        The DAG the labels index, in the ORACLE'S id space — the bottom rung
        of the degradation ladder (exact bidirectional online search when
        labels cannot be trusted).  Must be the graph of the SERVED epoch:
        owners with a mutating working graph (``repro.dynamic``) pass a
        frozen snapshot at every ``refresh``, never a live view.

    Degradation ladder
    ------------------
    Queries normally run device-side (kernel / dense / sharded).  A device
    backend failure downgrades the whole sub-batch to the host merge path
    (same labels, same verdicts); a query touching a *quarantined* label row
    (``set_quarantine`` — rows a non-strict snapshot load could not verify)
    skips labels entirely and runs the exact online search.  Every rung
    returns correct verdicts; ``self.degradation`` counts how often each
    downgrade fired so operators see corruption as a metric, not an outage.

    Memory budgets (three-valued verdicts)
    --------------------------------------
    ``set_budget`` installs a ``serve.budget.TruncatedStore`` — labels cut
    to a rank-prefix under a byte budget — and the engine serves from the
    truncated matrices.  Verdicts become three-valued: a HIT on surviving
    prefixes is a proven YES (every surviving entry is real); a MISS is a
    proven NO unless BOTH rows were truncated (a uniform rank threshold
    means kept entries can never match dropped entries, so the lost
    intersection lives entirely in dropped x dropped); the residue —
    both-rows-cut miss that no exact structural filter (same vertex, topo
    level) decides — is UNCERTAIN and routes to the exact-search rung.
    Wrong answers are impossible at any budget.  A batch captures its
    store view once at entry (the view tuple is swapped whole), so a
    concurrent re-truncation can never tear masks from matrices mid-batch.
    """

    def __init__(
        self,
        oracle,
        backend: str = "auto",
        level: Optional[np.ndarray] = None,
        mesh=None,
        data_axes: Optional[Sequence[str]] = None,
        model_axis: str = "model",
        bucketing: bool = True,
        n_tiers: int = 3,
        min_tile: int = 256,
        comp_source=None,
        epoch: int = 0,
        fallback_graph=None,
        search_node_budget: Optional[int] = None,
    ):
        self.oracle = oracle
        self.mesh = mesh
        self.backend = select_backend(backend, mesh)
        # own copy: the owner may keep mutating its working level array
        # between publishes (repro.dynamic), and queries must not see it
        self.level = None if level is None else np.array(level, dtype=np.int32)
        self.bucketing = bucketing
        self.min_tile = int(min_tile)
        self.n_tiers = int(n_tiers)
        if data_axes is None and mesh is not None:
            data_axes = tuple(ax for ax in mesh.axis_names if ax != model_axis)
        self.data_axes = data_axes
        self.model_axis = model_axis
        self.comp_source = comp_source
        self.epoch = int(epoch)
        self._lo, self._li, self._meta = self._place_labels(oracle)
        self.widths = tier_widths(
            oracle.out_len, oracle.in_len, oracle.max_label_len, n_tiers=n_tiers
        )
        self._sharded_fns: dict = {}
        # (backend, source L_out, source L_in, resharded pair): labels in a
        # per-call override's mesh layout, kept until the source changes
        self._resharded: Optional[tuple] = None
        self.last_stats: dict = {}
        self._fallback_graph = fallback_graph
        self._fallback_csr = None   # resolved (graph, reverse) pair, lazy
        self.quarantine_out: Optional[np.ndarray] = None
        self.quarantine_in: Optional[np.ndarray] = None
        # cumulative downgrade counters (ladder observability); mutated only
        # under _stats_lock so stats()/reset_stats() are atomic with respect
        # to in-flight query_batch tallies (the daemon reads stats() from
        # its publish worker thread while dispatches run in another)
        self.degradation = dict(_ZERO_DEGRADATION)
        self._stats_lock = threading.Lock()
        # node cap for the search rung (None = unbounded; the search stays
        # exact either way — exhaustion falls back to forward-only BFS)
        self.search_node_budget = search_node_budget
        # (store, device L_out, device L_in, tier widths, device vertex meta)
        # — swapped whole in set_budget so a batch's entry-time capture is
        # internally consistent
        self._budget_view: Optional[tuple] = None

    def _place_labels(self, oracle):
        """Device label matrices laid out for the default backend, and the
        per-vertex rows the fused program's prefilter reads
        (``_vertex_meta``); every upload (full, refreshed or budget-truncated
        labels) goes through here, so no batch uploads anything but its
        queries.  The mesh backends upload the matrices straight from the
        host arrays into their sharding (replicated, or split along the hop
        dim over the model axis), so no full copy ever lands on one
        device."""
        meta = self._vertex_meta(oracle)
        if self.backend not in ("sharded", "sharded_hop"):
            return (*oracle.device_labels(), meta)
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(None, self.model_axis) if self.backend == "sharded_hop" else P()
        sharding = NamedSharding(self.mesh, spec)
        return (jax.device_put(oracle.L_out, sharding),
                jax.device_put(oracle.L_in, sharding), meta)

    def _vertex_meta(self, oracle):
        """int32[n, 3] on the device, one row per vertex: ``out_len``,
        ``in_len``, ``level`` (int32[n, 2] without levels)."""
        cols = [oracle.out_len, oracle.in_len] + ([] if self.level is None else [self.level])
        return jnp.asarray(np.stack(cols, axis=1).astype(np.int32))

    # ---------------------------------------------------------- publishing

    def refresh(self, oracle, level: Optional[np.ndarray] = None,
                epoch: Optional[int] = None, fallback_graph=None) -> None:
        """Swap in a newly published label snapshot (epoch invalidation).

        Device label arrays and the tier-width plan refresh ONLY here — never
        mid-batch — so in-flight queries keep their pinned epoch's arrays.
        Tier widths are recomputed from the new length distribution, but when
        they come out unchanged (the common case for incremental repairs) the
        bucketed jit traces stay keyed to the same (rows, width) shapes and
        nothing retraces.
        """
        self.oracle = oracle
        if level is not None:
            self.level = np.array(level, dtype=np.int32)  # copy: see __init__
        self._lo, self._li, self._meta = self._place_labels(oracle)
        self.widths = tier_widths(
            oracle.out_len, oracle.in_len, oracle.max_label_len, n_tiers=self.n_tiers
        )
        self.epoch = self.epoch + 1 if epoch is None else int(epoch)
        _M_EPOCH.set(self.epoch)
        if fallback_graph is not None:
            self._fallback_graph = fallback_graph
        # the ladder's search rung must answer against the newly served
        # epoch's graph — drop the previous epoch's resolved snapshot
        self._fallback_csr = None
        # new labels supersede any previous load-time quarantine
        self.quarantine_out = None
        self.quarantine_in = None
        # ...and any budget truncation (it was cut from the OLD labels); the
        # daemon's BudgetController re-applies its budget on the next tick
        self._budget_view = None

    # ------------------------------------------------------- observability

    def stats(self) -> dict:
        """Consistent snapshot of the engine's serving state for health
        endpoints: taken under ``_stats_lock``, so a reader can never
        observe counters torn between two batches — ``_tally`` publishes a
        finished batch's counters and its ``last_stats`` record under the
        same lock, and a reader in another thread (the daemon's publish
        worker) sees either all of a batch or none of it."""
        bv = self._budget_view
        with self._stats_lock:
            return {
                "epoch": self.epoch,
                "backend": self.backend,
                "widths": list(self.widths),
                "n_quarantined": int(
                    (0 if self.quarantine_out is None else int(self.quarantine_out.sum()))
                    + (0 if self.quarantine_in is None else int(self.quarantine_in.sum()))),
                "budget": None if bv is None else {
                    "budget_bytes": bv[0].budget_bytes,
                    "resident_bytes": bv[0].resident_bytes,
                    "rank_cut": bv[0].rank_cut,
                    "n_truncated_rows": int(bv[0].truncated_out.sum()
                                            + bv[0].truncated_in.sum()),
                },
                "degradation": dict(self.degradation),
                "last_batch": copy.deepcopy(self.last_stats),
            }

    def reset_stats(self) -> None:
        """Zero the cumulative degradation counters and the last-batch
        record (e.g. at daemon startup, or between bench runs).  Atomic with
        respect to in-flight ``query_batch`` tallies: the counter dict is
        swapped whole under the lock, never cleared in place."""
        with self._stats_lock:
            self.degradation = dict(_ZERO_DEGRADATION)
            self.last_stats = {}

    # ------------------------------------------------- degradation ladder

    def set_quarantine(self, quarantine_out: Optional[np.ndarray],
                       quarantine_in: Optional[np.ndarray]) -> None:
        """Mark label rows that must not be trusted (``persist.LoadReport``
        masks from a non-strict snapshot load).  Queries touching them route
        to the online-search rung instead of reading the rows."""
        def _norm(q):
            if q is None or not np.any(q):
                return None
            return np.asarray(q, dtype=bool)

        self.quarantine_out = _norm(quarantine_out)
        self.quarantine_in = _norm(quarantine_in)

    @property
    def budget_store(self):
        """The active ``TruncatedStore`` (None = serving the full labels)."""
        bv = self._budget_view
        return None if bv is None else bv[0]

    def set_budget(self, store) -> None:
        """Install (or with None, remove) a budget-truncated label store.

        The engine keeps serving ``self.oracle``'s graph — only the label
        MATRICES read by the intersection backends switch to the truncated
        store, together with its truncation masks, a tier-width plan fit
        to the truncated length distribution and the truncated lengths'
        device rows.  All five swap as one tuple:
        an in-flight batch that captured the previous view stays internally
        consistent (see class docstring), which is what lets the daemon's
        pressure loop re-truncate between dispatches without draining."""
        if store is None:
            self._budget_view = None
            return
        t = store.oracle
        lo, li, meta = self._place_labels(t)
        widths = tier_widths(t.out_len, t.in_len, t.max_label_len,
                             n_tiers=self.n_tiers)
        self._budget_view = (store, lo, li, widths, meta)

    def _fallback(self):
        """Resolve the fallback graph to a cached (g, g_rev) pair."""
        if self._fallback_csr is None:
            g = self._fallback_graph
            if g is None:
                raise RuntimeError(
                    "degradation ladder exhausted: quarantined label rows "
                    "need the online-search rung, but no fallback_graph was "
                    "configured on this QueryEngine")
            if callable(g):
                g = g()
            self._fallback_csr = (g, g.reverse())
        return self._fallback_csr

    def _search_batch(self, rest: np.ndarray) -> np.ndarray:
        """Bottom rung: exact bidirectional search, no label reads."""
        from repro.core.baselines.online_search import bidirectional_query

        g, g_rev = self._fallback()
        out = np.empty(rest.shape[0], dtype=bool)
        for i, (u, v) in enumerate(rest):
            out[i] = bidirectional_query(g, g_rev, int(u), int(v),
                                         node_budget=self.search_node_budget)
        return out

    # ------------------------------------------------------------- queries

    def _map_ids(self, queries: np.ndarray) -> np.ndarray:
        comp = self.comp_source() if self.comp_source is not None else None
        if comp is None:
            return queries
        return comp[np.asarray(queries, dtype=np.int64)].astype(np.int32)

    def query(self, u: int, v: int) -> bool:
        """Single host query (prefilters + rank-ordered sorted merge)."""
        if self.comp_source is not None:
            comp = self.comp_source()
            u, v = int(comp[u]), int(comp[v])
        if u == v:
            return True
        if (self.quarantine_out is not None and self.quarantine_out[u]) or (
                self.quarantine_in is not None and self.quarantine_in[v]):
            # untrusted rows: even the length/level prefilters would read
            # corrupt state — go straight to the search rung
            with self._stats_lock:
                self.degradation["quarantined"] += 1
                self.degradation["searched"] += 1
            _DEGRADED_KIND["quarantined"].inc()
            _DEGRADED_KIND["searched"].inc()
            return bool(self._search_batch(np.asarray([[u, v]]))[0])
        if self.level is not None and self.level[u] >= self.level[v]:
            return False
        bv = self._budget_view
        o = self.oracle if bv is None else bv[0].oracle
        if o.out_len[u] == 0 or o.in_len[v] == 0:
            # an empty TRUNCATED row is only a proven miss when at most one
            # side was cut — fall through to the uncertain check below
            hit = False
        else:
            hit = o.query(u, v)
        if hit:
            return True          # hits on surviving prefixes are proven YES
        if bv is not None and bv[0].truncated_out[u] and bv[0].truncated_in[v]:
            # miss with BOTH rows cut: uncertain -> exact search rung
            with self._stats_lock:
                self.degradation["uncertain"] += 1
                self.degradation["searched"] += 1
            _DEGRADED_KIND["uncertain"].inc()
            _DEGRADED_KIND["searched"].inc()
            return bool(self._search_batch(np.asarray([[u, v]]))[0])
        return False

    def query_batch(self, queries: np.ndarray, backend: Optional[str] = None,
                    deadline: Optional[float] = None) -> np.ndarray:
        """Answer int[B, 2] queries -> bool[B].

        With ``comp_source`` set, queries are original vertex ids and the
        same-SCC short-circuit (the engine's ``u == v`` prefilter after
        mapping) reads the CURRENT condensation — not a cached copy.

        ``deadline`` (absolute ``time.monotonic()`` seconds) is the serving
        daemon's per-batch latency budget, propagated down here because the
        engine owns the one genuinely unpredictable step: a device dispatch
        can retrace (new tile/width shape) and stall for orders of magnitude
        longer than a warm call.  A batch already past its deadline
        therefore skips the device attempt and takes the predictable host
        merge (counted as ``deadline_to_host``).  Deadlines never change
        verdicts — every rung stays exact.
        """
        st = _STAGES.tick()
        with st("map"):
            queries = self._map_ids(np.asarray(queries))
            queries = np.ascontiguousarray(np.asarray(queries, dtype=np.int32))
            backend = self.backend if backend is None else select_backend(backend, self.mesh)
            # capture the budget view ONCE: everything this batch reads
            # (matrices, masks, widths) comes from one immutable tuple, so a
            # pressure-loop re-truncation landing mid-batch cannot mix old
            # masks with new rows
            bv = self._budget_view
            store = None if bv is None else bv[0]
            out = np.zeros(queries.shape[0], dtype=bool)
            degraded = dict(_ZERO_DEGRADATION)
            label_idx = np.arange(queries.shape[0])

        # ladder rung 0 (when needed): queries touching quarantined label
        # rows bypass prefilters TOO — length/level prefilters read the very
        # state that failed verification, and a zero-filled out_len would
        # flip verdicts to False.  Everything they need comes from the
        # fallback graph.
        if self.quarantine_out is not None or self.quarantine_in is not None:
            qm = np.zeros(queries.shape[0], dtype=bool)
            if self.quarantine_out is not None:
                qm |= self.quarantine_out[queries[:, 0]]
            if self.quarantine_in is not None:
                qm |= self.quarantine_in[queries[:, 1]]
            q_idx = np.nonzero(qm)[0]
            if q_idx.size:
                degraded["quarantined"] += int(q_idx.size)
                degraded["searched"] += int(q_idx.size)
                out[q_idx] = self._search_batch(queries[q_idx])
                label_idx = np.nonzero(~qm)[0]

        # the batch record is LOCAL until the batch finishes: _tally
        # publishes it (with the counter adds) atomically under _stats_lock,
        # so a concurrent stats()/reset_stats() never sees a half-built
        # record or tears a tally mid-batch
        stats = {
            "backend": backend,
            "n_queries": int(queries.shape[0]),
            "n_prefiltered": 0,
            "path": None,       # "fused" / "tiered": the device path that served
            "tiers": [],
            "degraded": degraded,
        }
        fused_error = None
        if (label_idx.size and self._fuses(backend, bv)
                and (deadline is None or time.monotonic() <= deadline)):
            try:
                res = self._device_batch(queries[label_idx], backend == "kernel",
                                         stats=stats, view=bv, st=st, fused=True)
                with st("scatter"):
                    out[label_idx] = res
            except Exception as e:  # ladder: the host prefilter + merge below
                fused_error = e
        if stats["path"] != "fused":
            self._host_prefilter_batch(queries, label_idx, out, backend, deadline,
                                       fused_error, stats=stats, view=bv, st=st)

        # three-valued epilogue: under a budget, a False verdict from the
        # labels (backend miss OR emptiness prefilter on a cut-to-empty
        # row) is only proven when at most one row was truncated.  The
        # same-vertex and topo-level prefilters are graph facts, exact at
        # any budget, so they keep their verdicts.
        if store is not None and store.any_truncated and label_idx.size:
            lq = queries[label_idx]
            unc = (store.truncated_out[lq[:, 0]]
                   & store.truncated_in[lq[:, 1]] & ~out[label_idx])
            unc &= lq[:, 0] != lq[:, 1]
            if self.level is not None:
                unc &= self.level[lq[:, 0]] < self.level[lq[:, 1]]
            unc_idx = label_idx[unc]
            if unc_idx.size:
                degraded["uncertain"] += int(unc_idx.size)
                degraded["searched"] += int(unc_idx.size)
                trace.event("degrade", cat="engine", kind="uncertain", n=int(unc_idx.size))
                out[unc_idx] = self._search_batch(queries[unc_idx])
        self._tally(stats, degraded)
        st.observe()
        return out

    def _host_prefilter_batch(self, queries, label_idx, out, backend, deadline,
                              fused_error, stats, view, st) -> None:
        """The label rows of a batch the fused program did not serve: the
        prefilter on the host, then the rest on the backend, or on the host
        merge where the backend is ``host``, the device failed
        (``fused_error``, or a failure here) or the deadline has passed."""
        o = self.oracle if view is None else view[0].oracle
        with st("prefilter"):
            pf = apply_prefilters(queries[label_idx], o.out_len, o.in_len, self.level)
            out[label_idx] = pf.decided & pf.value
            rest_idx = label_idx[~pf.decided]
            rest = queries[rest_idx]
            stats["n_prefiltered"] = int(label_idx.shape[0] - rest_idx.size)
        if not rest_idx.size:
            return
        degraded = stats["degraded"]
        if backend == "host":
            res = self._host_batch(rest, o)
        elif fused_error is not None:
            res = self._device_to_host(rest, o, backend, fused_error, degraded)
        elif deadline is not None and time.monotonic() > deadline:
            # past budget before the device attempt: retrace risk is
            # the one unbounded cost left — take the predictable path
            degraded["deadline_to_host"] += int(rest.shape[0])
            trace.event("degrade", cat="engine", kind="deadline_to_host",
                        n=int(rest.shape[0]))
            res = self._host_batch(rest, o)
        else:
            try:
                if backend in ("dense", "kernel"):
                    res = self._device_batch(
                        rest, use_kernel=backend == "kernel",
                        stats=stats, view=view, st=st)
                else:
                    res = self._sharded_batch(rest, backend, view=view, st=st)
            except Exception as e:  # ladder: device failure -> host merge
                res = self._device_to_host(rest, o, backend, e, degraded)
        with st("scatter"):
            out[rest_idx] = res

    def _device_to_host(self, rest: np.ndarray, o, backend: str, error: Exception,
                        degraded: dict) -> np.ndarray:
        """The ladder's device -> host rung: count it, say why, and serve
        ``rest`` on the host merge path."""
        degraded["device_to_host"] += int(rest.shape[0])
        trace.event("degrade", cat="engine", kind="device_to_host",
                    n=int(rest.shape[0]), error=type(error).__name__)
        warnings.warn(
            f"{backend!r} backend failed ({type(error).__name__}: {error}); "
            f"serving {rest.shape[0]} queries on the host merge path",
            stacklevel=4)
        return self._host_batch(rest, o)

    def warmup(self, max_batch: int, backend: Optional[str] = None) -> int:
        """Compile every device program a batch of up to ``max_batch``
        queries can dispatch: the fused program at each power-of-two tile,
        or each (tier width, tile) pair of the planner for a store too wide
        to fuse, or each daemon pad size without bucketing.

        Runs OUTSIDE the degradation ladder: a device program that fails
        here is a fault in the program, not a runtime device fault, so it
        raises instead of being served on the host.  Returns the number of
        programs run."""
        backend = self.backend if backend is None else select_backend(backend, self.mesh)
        if backend == "host":
            return 0
        bv = self._budget_view
        lo, li, widths, meta = ((self._lo, self._li, self.widths, self._meta)
                                if bv is None else bv[1:5])

        def ladder(lo_rows: int) -> list:  # powers of two up to max_batch
            sizes = [lo_rows]
            while sizes[-1] < max_batch:
                sizes.append(sizes[-1] * 2)
            return sizes

        use_kernel = backend == "kernel"
        if self._fuses(backend, bv):
            # a batch pads to a power of two from min_tile
            runs = [_tier_intersect_fused(lo, li, meta, jnp.zeros((r, 2), jnp.int32),
                                          use_kernel)
                    for r in ladder(self.min_tile)]
        elif backend in ("dense", "kernel") and self.bucketing:
            # the planner pads each tier to a power of two from min_tile
            runs = [_tier_intersect(lo, li, jnp.zeros((r, 2), jnp.int32), w, use_kernel)
                    for r in ladder(self.min_tile) for w in widths]
        else:
            # the daemon pads each batch to a power of two from 64, capped
            sizes = sorted({min(s, max_batch) for s in ladder(64)})
            if backend in ("dense", "kernel"):
                runs = [serve_step(lo, li, jnp.zeros((s, 2), jnp.int32),
                                   use_kernel=use_kernel) for s in sizes]
            else:
                runs = [self._sharded_batch(np.zeros((s, 2), np.int32), backend,
                                            view=bv) for s in sizes]
        jax.block_until_ready(runs)
        return len(runs)

    def _host_batch(self, rest: np.ndarray, o=None) -> np.ndarray:
        o = self.oracle if o is None else o
        return np.fromiter((o.query(int(u), int(v)) for u, v in rest), dtype=bool,
                           count=rest.shape[0])

    def _tally(self, stats: dict, degraded: dict) -> None:
        """Publish a finished batch: counters + last_stats flip together."""
        with self._stats_lock:
            for k, v in degraded.items():
                self.degradation[k] += v
            self.last_stats = stats
        _M_QUERIES.inc(stats["n_queries"])
        _M_PREFILTERED.inc(stats["n_prefiltered"])
        if stats["path"] is not None:
            _DEVICE_PATH[stats["path"]].inc()
        for k, v in degraded.items():
            if v:
                _DEGRADED_KIND[k].inc(v)

    # ------------------------------------------------------------ backends

    def _fuses(self, backend: str, view: Optional[tuple]) -> bool:
        """Whether the fused program serves a batch of this backend on this
        captured view: bucketed dense/kernel, store no wider than
        ``FUSED_MAX_WIDTH`` slots."""
        lo, li = (self._lo, self._li) if view is None else view[1:3]
        return (backend in ("dense", "kernel") and self.bucketing
                and max(lo.shape[1], li.shape[1]) <= FUSED_MAX_WIDTH)

    def _device_batch(self, rest: np.ndarray, use_kernel: bool,
                      stats: Optional[dict] = None,
                      view: Optional[tuple] = None, st=NO_TICK,
                      fused: bool = False) -> np.ndarray:
        """Verdicts for ``rest`` from the device.  With ``fused``, ``rest`` is
        every label row of the batch, unfiltered: one fused program
        prefilters and intersects it at the store's full width (a one-tier
        plan), and its prefiltered count lands in ``stats``.  Otherwise
        ``rest`` is what the host prefilter left, one program per tier (or
        one at the full width without bucketing)."""
        if stats is None:
            stats = {"tiers": []}   # direct callers outside query_batch
        if view is not None:
            o, lo, li, widths, meta = view[0].oracle, *view[1:5]
        else:
            o, lo, li, widths, meta = (self.oracle, self._lo, self._li, self.widths,
                                       self._meta)
        # chaos hook (inside enqueue): an injected device failure exercises
        # the ladder's device -> host downgrade in query_batch
        site = "kernel" if use_kernel else "dense"
        if not self.bucketing:
            with st("enqueue"):
                inject.fire("serve.device_dispatch", backend=site)
                r = serve_step(lo, li, jnp.asarray(rest), use_kernel=use_kernel)
            with st("sync"):
                return np.asarray(r)
        if fused:
            with st("enqueue"):
                plan = whole_batch_plan(rest.shape[0], max(lo.shape[1], li.shape[1]),
                                        min_tile=self.min_tile)
                q = plan.padded_queries(rest, plan.tiers[0])
                inject.fire("serve.device_dispatch", backend=site)
                results = [_tier_intersect_fused(lo, li, meta, jnp.asarray(q), use_kernel)]
        else:
            with st("plan"):
                plan = plan_batch(rest, o.out_len, o.in_len, widths, min_tile=self.min_tile)
                padded = [plan.padded_queries(rest, tier) for tier in plan.tiers]
            with st("enqueue"):
                inject.fire("serve.device_dispatch", backend=site)
                results = [_tier_intersect(lo, li, jnp.asarray(q), tier.width, use_kernel)
                           for q, tier in zip(padded, plan.tiers)]
        with st("sync"):
            host = [np.asarray(r) for r in results]
        with st("scatter"):
            if fused:   # decode; the pad rows' codes are dropped here
                codes = host[0][: rest.shape[0]]
                stats["n_prefiltered"] = int(np.count_nonzero(codes & _DECIDED))
                host = [codes & _HIT]
            out = plan.scatter(host)
            stats["tiers"].extend(
                {"width": tier.width, "count": int(tier.idx.size), "rows": tier.rows}
                for tier in plan.tiers)
            stats["path"] = "fused" if fused else "tiered"
        return out

    def _sharded_batch(self, rest: np.ndarray, backend: str,
                       view: Optional[tuple] = None, st=NO_TICK) -> np.ndarray:
        lo, li = (self._lo, self._li) if view is None else (view[1], view[2])
        made = self._sharded_fns.get(backend)
        if made is None:
            if backend == "sharded":
                made = make_sharded_serve_step(self.mesh, data_axes=self.data_axes)
            else:
                made = make_hop_sharded_serve_step(
                    self.mesh, model_axis=self.model_axis, data_axes=self.data_axes
                )
            self._sharded_fns[backend] = made
        fn, (label_sharding, _, _), _ = made
        if backend != self.backend:
            # labels are laid out for the default backend: a per-call
            # override reshards them once per label store, not per batch
            r = self._resharded
            if r is None or r[0] != backend or r[1] is not lo or r[2] is not li:
                r = (backend, lo, li, jax.device_put((lo, li), label_sharding))
                self._resharded = r
            lo, li = r[3]
        # fixed shapes across devices: pad the batch to a data-shard multiple
        shards = 1
        for ax in self.data_axes or ():
            shards *= self.mesh.shape[ax]
        B = rest.shape[0]
        pad = (-B) % max(shards, 1)
        if pad:
            rest = np.concatenate([rest, np.zeros((pad, 2), dtype=rest.dtype)], axis=0)
        with st("enqueue"):
            inject.fire("serve.device_dispatch", backend=backend)
            r = fn(lo, li, jnp.asarray(rest))
        with st("sync"):
            return np.asarray(r)[:B]
