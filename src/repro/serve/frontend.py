"""Async batched serving front-end for sharded dynamic indexes.

Pipeline (the latency-budget / capacity-class contract)::

    submit() -> request queue -> AdaptiveBatcher -> TenantPack.find -> scatter
                                     |                    |
                          coalesce up to the        one stacked shard_map
                          latency budget (or        dispatch over every
                          the batch-size cap)       tenant, padded to pow2
                                                    capacity classes

* **Coalescing**: requests wait at most ``ServeConfig.latency_budget_s``
  measured from the *oldest* queued request; a batch also cuts early when
  the queued key count reaches ``max_batch``.  Batching trades that bounded
  queueing delay for one dispatch amortized over every caller in the
  window.
* **Capacity-class padding**: the live batch pads to
  ``kernels.lookup.capacity_class`` widths (pow2, 128 floor), so the jitted
  stacked dispatch sees only pow2 query shapes — after warmup the hot path
  **never retraces**; batch-size variation changes pad contents, not
  shapes.  (``core.distributed.TRACE_COUNTS`` exposes the trace counter
  the guard tests pin.)
* **Multi-tenant stacked dispatch**: N independent ``ShardedDynamicIndex``
  tenants answer in one ``shard_map`` program
  (``core.distributed._tenant_stacked_find_fn``).  Tenants of different
  build sizes share the single trace: tiers pad to cross-tenant max
  capacity classes, leaf tables pad to the widest tenant with the last
  live leaf replicated (``lookup.pad_packed_leaves``), and per-tenant
  routing rescales ride the data — the traced ``route_n`` scalars on the
  jnp path, the ``pack_root(route_scale=...)`` fold on the kernel path.
* **Double-buffered dispatch**: up to ``pipeline_depth`` batches stay in
  flight; while batch k executes on device, the loop coalesces, stages
  (``jax.device_put``) and dispatches batch k+1, so the device never
  idles between batches.  Results resolve (one host sync per batch) and
  scatter back to each caller's future.
* **Find/update interleaving**: insert/delete requests coalesce into the
  same batches; they apply *before* the batch's finds dispatch (finds
  observe every update coalesced with them).  Mutations ride the PR 5
  dirty-row slice cache twice over — each tenant restacks only its dirty
  shard rows, and the tenant stack rewrites only the mutated tenants'
  rows (donated row scatters, true in-place writes).
* **Range requests** (``submit_range``): the ``"range"`` kind answers
  inclusive key ranges ``[lo, hi]`` with global live ranks
  ``(rank_lo, rank_hi)`` — leftmost rank of ``lo``, rightmost rank of
  ``hi`` under duplicates, tombstones excluded, ``rank_hi`` clamped so
  degenerate ranges come back empty.  Ranges coalesce into the same
  batches as point finds but dispatch through their own stacked program
  (``core.distributed._tenant_stacked_range_fn``) on a [lo block | hi
  block] query row with its own capacity class.  Both endpoints of every
  pair count toward the ``max_batch`` early-cut, so one scan-heavy caller
  can't starve the coalescer.
* **Typed requests**: every submission surface funnels through
  ``submit(Request(tenant, kind, payload))`` — the ``submit_*`` methods
  are thin constructors.  Payload validation (the kind filter, the
  finiteness rejection that protects the +inf-padded delta tier, range
  endpoint pairing) lives in exactly one place: the :class:`Request`
  constructor.
* **Idle-window drift maintenance**: when the queue drains after a batch,
  the dispatcher thread gives each tenant one pool hot-swap pass
  (``ShardedDynamicIndex.maybe_swap``) — drift-latched shards try the
  Lemma 4.1 bound-checked leaf swaps and ride the dirty-row slice cache
  back into the stacked state, so adaptation happens *between* batches
  with zero retraces and no refit stalls on the serving path.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core import distributed as dist_mod
from ..core.paths import resolve_path
from ..kernels.lookup import capacity_class, pad_packed_leaves

Array = jax.Array


@dataclass
class ServeConfig:
    """Front-end knobs (see module docstring for the contract)."""
    latency_budget_s: float = 2e-3    # max coalesce wait from oldest request
    max_batch: int = 4096             # early-cut key-count cap per batch
    batch_floor: int = 128            # capacity-class floor for query rows
    pipeline_depth: int = 2           # batches in flight (double-buffered)


REQUEST_KINDS = ("find", "range", "insert", "delete")


class Request:
    """One typed serving request — and the future its caller waits on.

    Validation lives HERE, in exactly one place, for every submission
    surface (``frontend.submit`` and the thin ``submit_*`` wrappers):

      * ``kind`` must be one of ``find | range | insert | delete`` — an
        unrecognized kind would fall through the dispatcher's kind
        filters and leave its caller waiting forever;
      * keys coerce to f64 and must be **finite**: a NaN/±inf insert or
        delete would poison the sorted delta tier (+inf is the delta pad
        sentinel, so a +inf insert silently corrupts every later merge),
        and a non-finite find/range key would walk the rank algebra into
        the exchange's +inf capacity padding;
      * a range's payload is the (2, n) ``[lo; hi]`` endpoint stack —
        endpoint arrays must pair up.
    """
    __slots__ = ("tenant", "kind", "keys", "arrival", "done_at", "found",
                 "rank", "rank_lo", "rank_hi", "error", "_event")

    def __init__(self, tenant: int, kind: str, keys,
                 arrival: float | None = None):
        if kind not in REQUEST_KINDS:
            raise ValueError(
                f"kind must be one of {REQUEST_KINDS}, got {kind!r}")
        keys = np.asarray(keys, np.float64)
        if kind == "range":
            if keys.ndim != 2 or keys.shape[0] != 2:
                raise ValueError(
                    "range payload must be the (2, n) [lo; hi] endpoint "
                    "stack: endpoint arrays must pair up")
        else:
            keys = np.atleast_1d(keys)
            if keys.ndim != 1:
                raise ValueError(f"{kind} payload must be a key vector, "
                                 f"got shape {keys.shape}")
        if not np.all(np.isfinite(keys)):
            raise ValueError(f"{kind} keys must be finite")
        self.tenant = int(tenant)
        self.kind = kind          # one of REQUEST_KINDS
        self.keys = keys          # (n,) keys; ranges carry (2, n) endpoints
        self.arrival = arrival    # stamped by submit() when None
        self.done_at = None               # completion time (frontend clock)
        self.found = None
        self.rank = None
        self.rank_lo = None
        self.rank_hi = None
        self.error = None
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """Block until served.  Finds return ``(found, rank)`` numpy
        arrays, ranges return ``(rank_lo, rank_hi)``; updates return
        ``None`` once applied."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request not served within {timeout}s")
        if self.error is not None:
            raise self.error
        if self.kind == "find":
            return self.found, self.rank
        if self.kind == "range":
            return self.rank_lo, self.rank_hi
        return None


class AdaptiveBatcher:
    """Pure coalescing policy — no threads, injectable clock, so the
    deadline semantics are unit-testable without wall-clock flakes.

    A batch becomes ready when the *oldest* pending request has waited the
    latency budget, or the queued key count reaches ``max_batch``.
    """

    def __init__(self, latency_budget_s: float, max_batch: int,
                 clock=time.monotonic):
        self.latency_budget_s = float(latency_budget_s)
        self.max_batch = int(max_batch)
        self.clock = clock
        self._pending: list[Request] = []
        self._n_keys = 0

    def __len__(self) -> int:
        return len(self._pending)

    def offer(self, req: Request) -> None:
        self._pending.append(req)
        self._n_keys += req.keys.size

    def deadline(self) -> float | None:
        """Absolute time the current batch must cut at (None when empty)."""
        if not self._pending:
            return None
        return self._pending[0].arrival + self.latency_budget_s

    def ready(self, now: float | None = None) -> bool:
        if not self._pending:
            return False
        if self._n_keys >= self.max_batch:
            return True
        return (self.clock() if now is None else now) >= self.deadline()

    def cut(self) -> list[Request]:
        batch, self._pending, self._n_keys = self._pending, [], 0
        return batch


class TenantPack:
    """N tenants' stacked per-shard state, padded to cross-tenant max
    capacity classes and maintained incrementally: ``find`` refreshes only
    the rows of tenants whose own slice cache changed (donated row
    scatters), and re-assembles cold only when a cross-tenant capacity
    class crosses a pow2."""

    def __init__(self, tenants: list, *, path: str = "auto",
                 use_kernel: bool | None = None,
                 interpret: bool | None = None):
        if not tenants:
            raise ValueError("TenantPack needs at least one tenant")
        mesh, axis = tenants[0].mesh, tenants[0].axis
        kinds = {t.shards[0].index.leaf_kind for t in tenants}
        if any(t.mesh is not mesh or t.axis != axis for t in tenants):
            raise ValueError("tenants must share one mesh and axis")
        if len(kinds) != 1:
            raise ValueError(f"tenants must share one leaf kind: {kinds}")
        use_kernel = resolve_path(
            path, f32_exact=lambda: all(t.f32_exact for t in tenants),
            use_kernel=use_kernel, what="tenant key space")
        self.tenants = tenants
        self.mesh, self.axis = mesh, axis
        self.use_kernel = bool(use_kernel)
        self.interpret = interpret if interpret is None else bool(interpret)
        self.leaf_kind = kinds.pop()
        self.n_leaves = max(t.n_leaves for t in tenants)
        # Common packed lane count: tenants re-pad to the widest tenant's
        # 128-multiple (pack_leaves layout).
        self._lp = -(-self.n_leaves // 128) * 128
        self._st: dict | None = None
        self._geom = None
        self._fps: list | None = None     # per-tenant identity fingerprints
        self.pack_full = 0                # cold tenant-stack assemblies
        self.pack_rows = 0                # tenant rows rewritten in place

    @property
    def n_tenants(self) -> int:
        return len(self.tenants)

    @property
    def n_shards(self) -> int:
        return self.tenants[0].n_shards

    # -- assembly ----------------------------------------------------------
    @staticmethod
    def _fingerprint(st: dict) -> tuple:
        """Identity snapshot of one tenant's stacked arrays.  Holding the
        refs keeps ids stable; comparison is pure ``is`` checks, so a
        tenant whose slice cache was untouched costs O(1) per batch."""
        leaves = jax.tree.leaves((st["root"], st["leaves"], st["packed"]))
        return tuple(st[k] for k in
                     dist_mod.ShardedDynamicIndex._ROW_KEYS) + \
            (st["offs"], st["splits"], st["iters"]) + tuple(leaves)

    def _tenant_row(self, t, st: dict, bcap: int, dcap: int) -> dict:
        """One tenant's (S, ...) slice set padded to the cross-tenant
        geometry — the unit of incremental tenant restacking."""
        L, lt = self.n_leaves, t.n_leaves
        padv = lambda a, c, v: a if a.shape[1] == c else jnp.pad(
            a, ((0, 0), (0, c - a.shape[1])), constant_values=v)
        pade = lambda a, c: a if a.shape[1] == c else jnp.pad(
            a, ((0, 0), (0, c - a.shape[1])) + ((0, 0),) * (a.ndim - 2),
            mode="edge")
        row = dict(
            splits=st["splits"],
            offs=st["offs"],
            # Per-tenant routing rescale as data: the stacked trace routes
            # with static n_leaves = max_t L_t, so a tenant built at L_t
            # scales its frozen per-shard route_n by L / L_t (overshoot
            # past L_t - 1 lands on the replicated last leaf below).
            route_n=st["route_n"] * (jnp.float64(L) / jnp.float64(lt)),
            base=padv(st["base"], bcap, jnp.inf),
            bdead=padv(st["bdead"], bcap, False),
            bpsum=pade(st["bpsum"], bcap + 1),
            dk=padv(st["dk"], dcap, jnp.inf),
            ddead=padv(st["ddead"], dcap, False),
            dpsum=pade(st["dpsum"], dcap + 1),
            root=st["root"],
            leaves=jax.tree.map(lambda a: pade(a, L), st["leaves"]),
            err_lo=pade(st["err_lo"], L),
            err_hi=pade(st["err_hi"], L))
        if self.use_kernel:
            kroot, kmat, kvec = t._packed_stack(st)
            kmat, kvec = pad_packed_leaves(kmat, kvec, lt, self._lp)
            row["kroot"], row["kmat"], row["kvec"] = kroot, kmat, kvec
        return row

    _STACK_KEYS = ("splits", "offs", "route_n", "base", "bdead", "bpsum",
                   "dk", "ddead", "dpsum", "err_lo", "err_hi")

    def _refresh(self) -> dict:
        sts = [t._stacked() for t in self.tenants]
        if self.use_kernel:
            for t, st in zip(self.tenants, sts, strict=True):
                t._packed_stack(st)
        bcap = max(st["bcap"] for st in sts)
        dcap = max(st["dcap"] for st in sts)
        fps = [self._fingerprint(st) for st in sts]
        geom = (bcap, dcap)
        if self._st is None or geom != self._geom:
            rows = [self._tenant_row(t, st, bcap, dcap)
                    for t, st in zip(self.tenants, sts, strict=True)]
            stack = lambda k: jnp.stack([r[k] for r in rows])
            lay = self.tenants[0].shard_sharding(lead=1)
            self._st = {k: stack(k) if k == "splits"
                        else jax.device_put(stack(k), lay)
                        for k in self._STACK_KEYS}
            tmap = lambda k: jax.tree.map(lambda *a: jnp.stack(a),
                                          *[r[k] for r in rows])
            self._st["root"] = tmap("root")
            self._st["leaves"] = tmap("leaves")
            if self.use_kernel:
                for k in ("kroot", "kmat", "kvec"):
                    self._st[k] = stack(k)
            self._geom = geom
            self.pack_full += 1
        else:
            stale = [i for i, fp in enumerate(fps)
                     if not all(a is b
                                for a, b in zip(fp, self._fps[i],
                                                strict=False))
                     or len(fp) != len(self._fps[i])]
            for i in stale:
                row = self._tenant_row(self.tenants[i], sts[i], bcap, dcap)
                idx = jnp.asarray([i])
                for k in self._STACK_KEYS + (
                        ("kroot", "kmat", "kvec") if self.use_kernel
                        else ()):
                    self._st[k] = dist_mod.scatter_rows_donated(
                        self._st[k], idx, row[k][None])
                scat = lambda dst, r, idx=idx: \
                    dist_mod.scatter_rows_donated(dst, idx, r[None])
                self._st["root"] = jax.tree.map(scat, self._st["root"],
                                                row["root"])
                self._st["leaves"] = jax.tree.map(scat, self._st["leaves"],
                                                  row["leaves"])
                self.pack_rows += 1
        self._fps = fps
        self._st["iters"] = max(st["iters"] for st in sts)
        return self._st

    # -- dispatch ----------------------------------------------------------
    def dispatch(self, kind: str, qmat) -> tuple:
        """``(program, args)`` of one stacked dispatch, for :meth:`find`
        (``kind="find"``) and :meth:`find_range` (``"range"``): calling
        ``program(*args)`` runs it, ``program.lower(*args)`` shows what it
        compiles to."""
        st = self._refresh()
        qmat = jnp.asarray(qmat, jnp.float64)
        T, w = qmat.shape
        per = self.n_shards * (2 if kind == "range" else 1)
        if T != self.n_tenants or w % per:
            raise ValueError(f"bad {kind} matrix {qmat.shape}: want "
                             f"({self.n_tenants}, k*{per})")
        make = dist_mod._tenant_stacked_range_fn if kind == "range" \
            else dist_mod._tenant_stacked_find_fn
        fn = make(self.mesh, self.axis, n_tenants=self.n_tenants,
                  n_leaves=self.n_leaves, leaf_kind=self.leaf_kind,
                  iters=st["iters"], use_kernel=self.use_kernel,
                  interpret=self.interpret)
        tables = (st["kroot"], st["kmat"], st["kvec"]) if self.use_kernel \
            else (st["root"], st["leaves"], st["err_lo"], st["err_hi"])
        return fn, (st["splits"], st["offs"], st["route_n"], st["base"],
                    st["bdead"], st["bpsum"], st["dk"], st["ddead"],
                    st["dpsum"], tables, qmat)

    def find(self, qmat) -> tuple[Array, Array]:
        """One stacked dispatch: ``qmat`` is (n_tenants, qcap) f64 with
        finite pads (qcap a multiple of the shard count; callers pad to
        ``capacity_class`` widths to stay on the warm trace).  Returns
        (found, rank) as (n_tenants, qcap) device arrays — asynchronous,
        so callers can overlap the next batch's staging."""
        fn, args = self.dispatch("find", qmat)
        return fn(*args)

    def find_range(self, rmat) -> tuple[Array, Array]:
        """One stacked range dispatch: ``rmat`` is (n_tenants, 2 * rcap)
        f64 laid out [lo endpoints | hi endpoints] per row (rcap a multiple
        of the shard count, finite pads).  Returns (rank_lo, rank_hi) as
        (n_tenants, rcap) device arrays with rank_hi clamped to rank_lo —
        same asynchrony contract as :meth:`find`."""
        fn, args = self.dispatch("range", rmat)
        rl, rr = fn(*args)
        rcap = rmat.shape[1] // 2
        rank_lo = rl[:, :rcap]
        return rank_lo, jnp.maximum(rr[:, rcap:], rank_lo)


@dataclass
class FrontendStats:
    batches: int = 0              # stacked dispatches
    queries: int = 0              # live find keys served
    ranges: int = 0               # live range pairs served
    updates: int = 0              # insert/delete keys applied
    swaps: int = 0                # drift-maintenance pool hot-swaps
    padded_slots: int = 0         # pad lanes dispatched (wasted work)
    qcaps: set = field(default_factory=set)   # capacity classes seen

    @property
    def pad_fraction(self) -> float:
        tot = self.queries + 2 * self.ranges + self.padded_slots
        return self.padded_slots / tot if tot else 0.0


class _InFlight:
    __slots__ = ("found", "rank", "plan", "rank_lo", "rank_hi", "rplan")

    def __init__(self, found, rank, plan, rank_lo=None, rank_hi=None,
                 rplan=()):
        self.found, self.rank, self.plan = found, rank, plan
        self.rank_lo, self.rank_hi, self.rplan = rank_lo, rank_hi, rplan


class BatchingFrontend:
    """The serving loop: a dispatcher thread drains the request queue
    through the batcher into stacked dispatches (module docstring).  Use
    as a context manager, or ``start()``/``stop()`` explicitly."""

    def __init__(self, tenants: list, *, path: str = "auto",
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 config: ServeConfig | None = None, clock=time.monotonic):
        self.config = config or ServeConfig()
        self.pack = TenantPack(tenants, path=path, use_kernel=use_kernel,
                               interpret=interpret)
        self.stats = FrontendStats()
        self.clock = clock
        self.batcher = AdaptiveBatcher(self.config.latency_budget_s,
                                       self.config.max_batch, clock)
        self._cond = threading.Condition()
        self._inflight: deque[_InFlight] = deque()
        self._stop = False
        self._thread: threading.Thread | None = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "BatchingFrontend":
        if self._thread is not None:
            raise RuntimeError("frontend already started")
        self._stop = False
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-frontend", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()

    def warmup(self, batch_sizes=(1,)) -> None:
        """Trace the stacked find AND range dispatches for each capacity
        class the given live batch sizes land in (plus the floor), so
        steady-state serving never pays a trace.  Call before opening the
        queue to traffic."""
        for n in {capacity_class(int(n), self.config.batch_floor)
                  for n in batch_sizes} | {self.config.batch_floor}:
            qcap = max(n, self.pack.n_shards)
            found, rank = self.pack.find(
                jnp.zeros((self.pack.n_tenants, qcap), jnp.float64))
            rlo, rhi = self.pack.find_range(
                jnp.zeros((self.pack.n_tenants, 2 * qcap), jnp.float64))
            jax.block_until_ready((found, rank, rlo, rhi))

    # -- submission --------------------------------------------------------
    def submit(self, request: Request) -> Request:
        """THE submission verb: enqueue one constructed :class:`Request`.
        Payload validation (finiteness, kind filter, range pairing)
        already ran on the Request constructor — this only checks the
        frontend-level facts (started, known tenant), stamps the arrival
        clock, and offers the request to the coalescer.  The ``submit_*``
        convenience wrappers below all funnel through here."""
        if self._thread is None:
            raise RuntimeError("frontend not started")
        if not 0 <= request.tenant < self.pack.n_tenants:
            raise ValueError(f"unknown tenant {request.tenant}")
        if request.arrival is None:
            request.arrival = self.clock()
        with self._cond:
            self.batcher.offer(request)
            self._cond.notify_all()
        return request

    def submit_find(self, tenant: int, keys) -> Request:
        return self.submit(Request(tenant, "find", keys))

    def submit_range(self, tenant: int, lo_keys, hi_keys) -> Request:
        """Inclusive key ranges ``[lo, hi]`` -> ``(rank_lo, rank_hi)``
        global live ranks (module docstring).  Both endpoint arrays count
        toward the batch key cap."""
        lo = np.atleast_1d(np.asarray(lo_keys, np.float64))
        hi = np.atleast_1d(np.asarray(hi_keys, np.float64))
        if lo.shape != hi.shape:
            raise ValueError(
                "range payload must be the (2, n) [lo; hi] endpoint "
                "stack: endpoint arrays must pair up")
        return self.submit(Request(tenant, "range", np.stack([lo, hi])))

    def submit_insert(self, tenant: int, keys) -> Request:
        return self.submit(Request(tenant, "insert", keys))

    def submit_delete(self, tenant: int, keys) -> Request:
        return self.submit(Request(tenant, "delete", keys))

    def lookup(self, tenant: int, keys, timeout: float | None = 60.0):
        """Synchronous convenience: submit one find and wait."""
        return self.submit_find(tenant, keys).result(timeout)

    def scan(self, tenant: int, lo_keys, hi_keys,
             timeout: float | None = 60.0):
        """Synchronous convenience: submit one range request and wait."""
        return self.submit_range(tenant, lo_keys, hi_keys).result(timeout)

    # -- the serving loop --------------------------------------------------
    def _collect(self) -> list | None:
        """Block for the next batch: wait for a first request, then
        coalesce until the batcher's deadline (or size cap).  Returns None
        on shutdown with nothing pending."""
        with self._cond:
            while not len(self.batcher):
                if self._stop:
                    return None
                self._cond.wait(timeout=0.05)
            while not self._stop and not self.batcher.ready():
                dl = self.batcher.deadline()
                self._cond.wait(timeout=max(dl - self.clock(), 0.0))
            return self.batcher.cut()

    def _apply_updates(self, batch: list) -> None:
        """Mutations coalesced into this batch apply before its finds
        dispatch — each tenant's dirty-row slice cache (and the tenant
        stack above it) then refreshes O(touched) at assembly."""
        for req in batch:
            if req.kind in ("find", "range"):
                continue
            try:
                tenant = self.pack.tenants[req.tenant]
                if req.kind == "insert":
                    tenant.insert_batch(req.keys)
                else:
                    tenant.delete_batch(req.keys)
                self.stats.updates += req.keys.size
            except Exception as e:          # broad: fail the caller
                req.error = e
            req.done_at = self.clock()
            req._event.set()

    def _dispatch(self, batch: list) -> _InFlight | None:
        finds = [r for r in batch if r.kind == "find"]
        rngs = [r for r in batch if r.kind == "range"]
        if not finds and not rngs:
            return None
        found = rank = rlo = rhi = None
        plan, rplan = [], []            # (req, tenant, start, stop)
        self.stats.batches += 1
        if finds:
            counts = [0] * self.pack.n_tenants
            for r in finds:
                t = r.tenant
                plan.append((r, t, counts[t], counts[t] + r.keys.size))
                counts[t] += r.keys.size
            qcap = capacity_class(max(counts), self.config.batch_floor)
            qcap = max(qcap, self.pack.n_shards)
            qmat = np.zeros((self.pack.n_tenants, qcap), np.float64)
            for r, t, a, b in plan:
                qmat[t, a:b] = r.keys
            live = sum(counts)
            self.stats.queries += live
            self.stats.padded_slots += qmat.size - live
            self.stats.qcaps.add(qcap)
            # Stage host->device explicitly, then dispatch asynchronously:
            # with pipeline_depth > 1 this batch's transfer and compute
            # overlap the previous batch's compute and the next batch's
            # coalescing.
            found, rank = self.pack.find(jax.device_put(qmat))
        if rngs:
            # Ranges ride their own [lo block | hi block] matrix with an
            # independent capacity class (range traffic is usually far
            # sparser than point traffic — padding one to the other's
            # width would double the wasted lanes).
            rcounts = [0] * self.pack.n_tenants
            for r in rngs:
                t = r.tenant
                n = r.keys.shape[1]
                rplan.append((r, t, rcounts[t], rcounts[t] + n))
                rcounts[t] += n
            rcap = capacity_class(max(rcounts), self.config.batch_floor)
            rcap = max(rcap, self.pack.n_shards)
            rmat = np.zeros((self.pack.n_tenants, 2 * rcap), np.float64)
            for r, t, a, b in rplan:
                rmat[t, a:b] = r.keys[0]
                rmat[t, rcap + a:rcap + b] = r.keys[1]
            rlive = sum(rcounts)
            self.stats.ranges += rlive
            self.stats.padded_slots += rmat.size - 2 * rlive
            self.stats.qcaps.add(rcap)
            rlo, rhi = self.pack.find_range(jax.device_put(rmat))
        return _InFlight(found, rank, plan, rlo, rhi, rplan)

    def _resolve(self, inf: _InFlight) -> None:
        now = self.clock()
        if inf.plan:
            # sync: ok(the one host sync per batch: point results resolve)
            found = np.asarray(inf.found)
            rank = np.asarray(inf.rank)  # sync: ok(rides the found sync)
            for req, t, a, b in inf.plan:
                req.found = found[t, a:b]
                req.rank = rank[t, a:b]
                req.done_at = now
                req._event.set()
        if inf.rplan:
            # sync: ok(range leg of the same batch resolution point)
            rlo = np.asarray(inf.rank_lo)
            rhi = np.asarray(inf.rank_hi)  # sync: ok(rides the rlo sync)
            for req, t, a, b in inf.rplan:
                req.rank_lo = rlo[t, a:b]
                req.rank_hi = rhi[t, a:b]
                req.done_at = now
                req._event.set()

    def _fail(self, batch: list, err: Exception) -> None:
        for req in batch:
            if not req._event.is_set():
                req.error = err
                req.done_at = self.clock()
                req._event.set()

    def _maintain(self) -> None:
        """Idle-window drift maintenance, run on the dispatcher thread
        between batches when the queue has drained: one pool hot-swap pass
        per tenant (``ShardedDynamicIndex.maybe_swap`` — per-leaf Lemma
        4.1 bound-checked commits on the drift-latched shards, riding the
        dirty-row slice cache).  Swaps rewrite stacked row *contents*,
        never shapes or search depths, so the warm find/range traces
        survive — the serve TRACE_COUNTS guard pins zero retraces across
        swap commits.  The same pass also runs the deferred-refit sweep:
        in swap mode the insert path never does structural work, so
        budget-exhausted leaves a swap could not absorb take their O(n)
        merge + refit HERE, in the idle window, off the serving path
        (refits may legitimately retrace — they change base shapes and
        can widen the clamped search depth).  Tenants without drift
        monitoring short-circuit on a host flag; the per-pass cost for
        monitored tenants is the one drift-table sync inside
        ``maybe_swap``."""
        for t in self.pack.tenants:
            swap = getattr(t, "maybe_swap", None)
            if swap is not None:
                self.stats.swaps += swap()

    def _loop(self) -> None:
        while True:
            batch = self._collect()
            if batch is None:
                break
            try:
                self._apply_updates(batch)
                inf = self._dispatch(batch)
            except Exception as e:          # broad: fail the batch
                self._fail(batch, e)
                continue
            if inf is not None:
                self._inflight.append(inf)
            while len(self._inflight) >= self.config.pipeline_depth or \
                    (self._inflight and not len(self.batcher)):
                self._resolve(self._inflight.popleft())
            if not len(self.batcher):
                self._maintain()
        while self._inflight:
            self._resolve(self._inflight.popleft())
