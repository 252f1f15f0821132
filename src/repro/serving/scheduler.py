"""Continuous-batching lanes + the discrete-event loop they run under.

Two layers live here:

* ``ContinuousBatcher`` — slot-based continuous batching over the ragged
  decode path. One BATCHED cache pytree holds ``n_slots`` lanes; requests
  are admitted into free lanes (prefill or cache-hit load writes the
  lane), every tick decodes ALL active lanes in one model call with
  per-lane write slots and RoPE positions (`decode_step(cur_index=(B,),
  position=(B,))` — the vector form added for exactly this), finished
  lanes free immediately and new requests stream in: no batch-boundary
  stalls. Token content is computed for real on the smoke model while
  simulated time uses the full-scale ``timemodel``.

* ``EventLoop`` — a priority event queue (arrival / load-complete /
  prefill-complete / decode-tick / write-complete) with a monotonic
  simulated clock and a zero-progress livelock guard. The I/O model is
  fully duplex-async: KV loads and prefills are *booked* on read /
  compute channels, and every byte movement INTO a tier (insert
  write-back, MCKP demotion, speculative prefetch promotion) is booked
  on the destination tier's write channel, completing via
  ``EV_WRITE_DONE``. Decode ticks never stall on storage: a lane joins
  the batch only when its load-complete event fires, and a fetch of a
  still-writing entry fences on the in-flight transfer.
  ``repro.serving.engine.ServingEngine`` is the full AdaptCache front
  end on top of this; ``run_continuous`` below is the thin
  single-batcher harness used by the scheduler tests.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import heapq
import itertools
import weakref
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnKind, LayerKind, ModelConfig
from repro.core.compression.base import KVData, StoredKV
from repro.models import Model
from repro.runtime.spans import mark, span
from repro.serving.chunking import PAGE_TOKENS
from repro.serving.runner import _layer_cache_refs
from repro.serving.timemodel import TimeModel
from repro.serving.workload import Request

# what a lane admits: a host KVData, or pieces in token order, each a
# fetched page in its stored form or a host KVData
LaneRun = Union[KVData, Sequence[Union[KVData, StoredKV]]]


@dataclasses.dataclass
class SlotState:
    req: Optional[Request] = None
    ttft_s: Optional[float] = None
    started_s: float = 0.0
    write_slot: int = 0              # next cache slot for this lane
    position: int = 0                # next RoPE position
    pending: List[int] = dataclasses.field(default_factory=list)
    generated: List[int] = dataclasses.field(default_factory=list)
    # fraction of dense KV bytes this lane's context costs per decode
    # read: < 1.0 when the matched prefix stays packed in HBM and the
    # fused kernel dequantizes it in VREGs (1.0 = dense pricing)
    kv_frac: float = 1.0

    @property
    def active(self) -> bool:
        return self.req is not None


@dataclasses.dataclass
class ScheduledResult:
    req_id: int
    context_key: str
    ttft_s: float
    finish_s: float
    tokens: List[int]
    # lane ran out of cache capacity before the answer completed; when it
    # happened mid-question the TTFT is fabricated — aggregates must
    # exclude truncated results (see ``summarize``)
    truncated: bool = False


_DECODE_CACHE: Dict[int, Tuple[Any, Any]] = {}   # id(model) -> (ref, fn)


def _shared_decode(model: Model):
    """One jitted decode_step per model instance: batchers are rebuilt per
    engine run, so sharing the jit wrapper avoids re-tracing every time.
    Model is a frozen dataclass, so the cache lives here, keyed by id with
    a weakref liveness check (a recycled id just re-jits)."""
    ent = _DECODE_CACHE.get(id(model))
    if ent is not None and ent[0]() is model:
        return ent[1]
    for k in [k for k, (r, _) in _DECODE_CACHE.items() if r() is None]:
        del _DECODE_CACHE[k]                     # drop dead entries
    fn = jax.jit(model.decode_step)
    _DECODE_CACHE[id(model)] = (weakref.ref(model), fn)
    return fn


def _lane_layout(cache, cfg: ModelConfig) -> Tuple[tuple, ...]:
    """Where each KVData array lands in the batched cache: one entry per
    cache leaf, ``(section, block index, block key, name, stacked,
    layers)``, with ``layers`` the leaf's layers as indices into the
    array's leading axis (attention layers for k/v/ckv/krope, Mamba
    layers for ssm/conv), in the leaf's group order."""
    leaves: Dict[tuple, List[Tuple[int, int]]] = {}
    ai = mi = 0
    for _, kind, (sect, j, g) in _layer_cache_refs(cache, cfg):
        if kind == LayerKind.MAMBA:
            sub, names, idx = "mamba", ("ssm", "conv"), mi
            mi += 1
        else:
            sub, idx = "self", ai
            names = (("ckv", "krope") if cfg.attn_kind == AttnKind.MLA
                     else ("k", "v"))
            ai += 1
        for name in names:
            leaves.setdefault((sect, j, sub, name, g is not None),
                              []).append((g or 0, idx))
    return tuple(key + (tuple(i for _, i in sorted(refs)),)
                 for key, refs in leaves.items())


def _layers(x: jax.Array, layers: Tuple[int, ...]) -> jax.Array:
    lo = layers[0]
    if layers == tuple(range(lo, lo + len(layers))):
        return x[lo:lo + len(layers)]
    return x[np.asarray(layers)]


@functools.partial(jax.jit, static_argnames=("layout",),
                   donate_argnames=("cache",))
def _write_window(cache, rows, state, lane, off, n, layout):
    """Write one window of a piece into lane ``lane`` of the donated
    cache, in place, cast to the cache's dtypes.

    ``rows`` maps each token array to its ``(layers*W, F)`` window, whose
    first ``n`` rows go to lane rows ``[off, off + n)``; rows past ``n``
    leave the lane as it was. Where ``off + W`` passes the lane's end the
    window starts earlier (``dynamic_update_slice`` would clamp its start)
    and its rows shift to match. ``state`` maps each whole-lane array
    (Mamba) to its ``(layers*R, F)`` matrix. ``off``, ``n`` and ``lane``
    are traced: one compile serves every run of a window's shape."""
    counts = collections.Counter()
    for _, _, _, name, _, layers in layout:
        counts[name] += len(layers)
    for sect, j, sub, name, stacked, layers in layout:
        blk = cache[sect][j][sub]
        leaf = blk[name]
        lead = (0,) if stacked else ()
        if name in rows:
            x = rows[name].reshape(counts[name], -1, rows[name].shape[-1])
            w, cap = x.shape[1], leaf.shape[len(lead) + 1]
            start = jnp.minimum(off, cap - w)
            shift = off - start
            r = jnp.arange(w)
            keep = (r >= shift) & (r < shift + n)
            x = jnp.roll(_layers(x, layers), shift, axis=1)
            x = x.reshape(x.shape[:2] + leaf.shape[len(lead) + 2:])
            x = jnp.expand_dims(x if stacked else x[0], len(lead))
            rest = (0,) * (leaf.ndim - len(lead) - 2)
            idx = lead + (lane, start) + rest
            old = jax.lax.dynamic_slice(leaf, idx, x.shape)
            keep = keep.reshape((1,) * (len(lead) + 1) + (w,)
                                + (1,) * len(rest))
            blk[name] = jax.lax.dynamic_update_slice(
                leaf, jnp.where(keep, x.astype(leaf.dtype), old), idx)
        elif name in state:
            x = state[name].reshape((-1,) + leaf.shape[len(lead) + 1:])
            x = _layers(x, layers)
            x = jnp.expand_dims(x if stacked else x[0], len(lead))
            idx = lead + (lane,) + (0,) * (leaf.ndim - len(lead) - 1)
            blk[name] = jax.lax.dynamic_update_slice(
                leaf, x.astype(leaf.dtype), idx)
    return cache


def _positions(arrays) -> int:
    return len(arrays["positions"]) if "positions" in arrays else 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _windows(x: jax.Array, layers: int, w: int) -> Tuple[jax.Array, ...]:
    """``(layers*T, F)`` rows as the ``(layers*w, F)`` windows that cover
    them, the last padded with zeros."""
    x = x.reshape(layers, -1, x.shape[-1])
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % w), (0, 0)))
    return tuple(x[:, i:i + w].reshape(-1, x.shape[-1])
                 for i in range(0, x.shape[1], w))


class ContinuousBatcher:
    """``device`` (default: JAX's default device) holds this batcher's
    params and lane cache, so every decode tick and lane write runs
    there — one engine replica per chip. ``page_tokens`` is the window a
    lane write moves at a time: the page size of the runs it admits."""

    def __init__(self, model: Model, params, time_model: TimeModel,
                 n_slots: int = 4, capacity: int = 1024, device=None,
                 page_tokens: int = PAGE_TOKENS):
        self.model = model
        self.tm = time_model
        self.n_slots = n_slots
        self.capacity = capacity
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        with jax.default_device(self.device):
            self.cache = jax.device_put(
                model.init_cache(batch=n_slots, capacity=capacity),
                self.device)
        self.slots = [SlotState() for _ in range(n_slots)]
        self._decode = _shared_decode(model)
        self.window = min(page_tokens, capacity)
        self._layout = _lane_layout(self.cache, model.cfg)
        # name -> (leaf dtype, layers, shape a layer's rows or state has
        # in the lane); token arrays have sub-block "self"
        self._arrays: Dict[str, Tuple[Any, int, Tuple[int, ...]]] = {}
        self._tokens = set()
        for sect, j, sub, name, stacked, layers in self._layout:
            leaf = self.cache[sect][j][sub][name]
            dt, n, _ = self._arrays.get(name, (leaf.dtype, 0, ()))
            per = leaf.shape[(2 if stacked else 1) + (sub == "self"):]
            self._arrays[name] = (dt, n + len(layers), per)
            if sub == "self":
                self._tokens.add(name)
        # uncommitted arrays on the default device share their compiled
        # programs with the program's other calls (the KIVI kernels that
        # set-up compiles among them); a replica elsewhere commits its own
        self._put = (jnp.asarray if self.device == jax.devices()[0]
                     else functools.partial(jax.device_put,
                                            device=self.device))
        self._warm()

    # -- lane loading ---------------------------------------------------------
    def _warm(self) -> None:
        """Compile the lane writer for a page window of a fetched page
        (float32) and of a host piece (the cache's dtypes, with any
        state); writes no row."""
        for host in (False, True):
            rows, state = {}, {}
            for name, (dt, n, per) in self._arrays.items():
                dt = dt if host else np.float32
                if name in self._tokens:
                    rows[name] = self._put(jnp.zeros(
                        (n * self.window, int(np.prod(per))), dt))
                elif host:
                    state[name] = self._put(jnp.zeros(
                        (n * int(np.prod(per[:-1])), per[-1]), dt))
            self.cache = _write_window(
                self.cache, rows, state, np.int32(0), np.int32(0),
                np.int32(0), layout=self._layout)

    def _write_lane(self, lane: int, run: LaneRun) -> int:
        """Write a run into cache lane ``lane``, piece after piece in
        token order; returns the number of occupied slots.

        ``run`` is a host KVData or a sequence of pieces, each a fetched
        ``StoredKV`` or a host KVData (a prefill's arrays). A stored piece
        goes to the device as stored (KIVI codes, scales and zeros, or
        lossless float32 rows) and is decompressed there; a host piece is
        cast on the host to the cache dtype. Either is written a window
        of ``self.window`` rows at a time by one donated program. The
        ``lane_write`` span carries the bytes that cross to the device
        and the pieces of each kind."""
        pieces = [run] if isinstance(run, dict) else list(run)
        n_stored = sum(isinstance(p, StoredKV) for p in pieces)
        h2d = sum(p.device_nbytes if isinstance(p, StoredKV)
                  else sum(int(np.size(a)) * self._arrays[name][0].itemsize
                           for name, a in p.items() if name in self._arrays)
                  for p in pieces)
        off = 0
        with span("lane_write", h2d_bytes=h2d, pages=n_stored,
                  host_pieces=len(pieces) - n_stored):
            for p in pieces:
                if isinstance(p, StoredKV):
                    off = self._write_stored(lane, p, off)
                else:
                    off = self._write_host(lane, p, off)
        return off

    def _write_stored(self, lane: int, p: StoredKV, off: int) -> int:
        with span("decompress", method=p.entry.method):
            mats = p.codec.decompress_device(p.entry, self._put)
        t = p.n_tokens
        w = self.window
        wins = {}
        for name, m in mats.items():
            if name in self._tokens:
                n = self._arrays[name][1]
                wins[name] = ([m] if m.shape[0] == n * w
                              else list(_windows(m, n, w)))
        state = {name: m for name, m in mats.items()
                 if name in self._arrays and name not in self._tokens}
        return self._write_windows(lane, wins, state, off,
                                   t if wins else _positions(p.entry.arrays))

    def _write_host(self, lane: int, kv: KVData, off: int) -> int:
        w = self.window
        wins, state = {}, {}
        t = 0
        for name, a in kv.items():
            if name not in self._arrays:
                continue
            dt, n, _ = self._arrays[name]
            if name in self._tokens:
                t = a.shape[1]
                wins[name] = []
                for lo in range(0, t, w):
                    x = self._put(np.asarray(a[:, lo:lo + w], dt)
                                  .reshape(-1, a.shape[-1]))
                    wins[name].append(x if t - lo >= w
                                      else _windows(x, n, w)[0])
            else:
                state[name] = self._put(
                    np.asarray(a, dt).reshape(-1, a.shape[-1]))
        return self._write_windows(lane, wins, state, off,
                                   t if wins else _positions(kv))

    def _write_windows(self, lane: int, wins: Dict[str, List[jax.Array]],
                       state: Dict[str, jax.Array], off: int, t: int) -> int:
        """Write a piece of ``t`` rows at lane row ``off``, one window per
        call, the whole-lane state with each; returns the next row."""
        if off + t > self.capacity:
            raise ValueError(f"a run of {off + t} rows overflows a lane of "
                             f"{self.capacity}")
        w = self.window
        if not t:
            wins = {}
        for i in range(-(-t // w) if wins else int(bool(state))):
            self.cache = _write_window(
                self.cache, {name: ws[i] for name, ws in wins.items()},
                state, np.int32(lane), np.int32(off + i * w),
                np.int32(min(w, t - i * w) if wins else 0),
                layout=self._layout)
        return off + t

    def free_lanes(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def admit(self, lane: int, req: Request, kv: LaneRun, orig_len: int,
              now: float, kv_frac: float = 1.0) -> None:
        """Write ``kv`` (a host KVData, or a run of pieces: see
        ``_write_lane``) into lane ``lane`` and start ``req`` there."""
        with span("admit", req_id=req.req_id):
            n_kept = self._write_lane(lane, kv)
            self.slots[lane] = SlotState(
                req=req, started_s=now, write_slot=n_kept,
                position=orig_len,
                pending=list(np.asarray(req.question, np.int64)),
                kv_frac=kv_frac)
        mark("admitted", req_id=req.req_id)

    def _decode_kvb(self, active: List[int]) -> Optional[float]:
        """Per-token KV-read bytes override for the next decode step:
        the position-weighted mean of the active lanes' ``kv_frac``
        applied to the dense per-token footprint. None (use the dense
        default) when every lane prices dense — the common case, kept
        bit-identical to the pre-fused path."""
        if all(self.slots[i].kv_frac >= 1.0 for i in active):
            return None
        pos_sum = sum(self.slots[i].position for i in active)
        if pos_sum <= 0:
            return None
        frac = (sum(self.slots[i].position * self.slots[i].kv_frac
                    for i in active) / pos_sum)
        return self.tm.cfg.kv_bytes_per_token() * frac

    def next_dt(self) -> Optional[float]:
        """Service time the next ``tick`` will charge (None when all
        lanes are idle) — lets the unified-compute path book the decode
        step on a channel BEFORE running it."""
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return None
        max_ctx = max(self.slots[i].position for i in active)
        return self.tm.decode_step_s(len(active), max_ctx,
                                     kv_bytes_per_token=self._decode_kvb(
                                         active))

    # -- one decode tick over all active lanes -------------------------------
    def tick(self, now: float) -> Tuple[List[ScheduledResult], float]:
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return [], 0.0
        tokens = np.zeros((self.n_slots, 1), np.int32)
        write = np.zeros((self.n_slots,), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        for i in active:
            s = self.slots[i]
            tokens[i, 0] = (s.pending[0] if s.pending
                            else (s.generated[-1] if s.generated else 0))
            write[i] = min(s.write_slot, self.capacity - 1)
            pos[i] = s.position
        with span("decode_tick", lanes=len(active),
                  positions=int(pos.sum())):
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(write),
                jnp.asarray(tokens), jnp.asarray(pos))
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))

        max_ctx = max(self.slots[i].position for i in active)
        dt = self.tm.decode_step_s(len(active), max_ctx,
                                   kv_bytes_per_token=self._decode_kvb(
                                       active))

        done: List[ScheduledResult] = []
        for i in active:
            s = self.slots[i]
            s.write_slot += 1
            s.position += 1
            if s.pending:
                s.pending.pop(0)
                if not s.pending:
                    # logits of the LAST question token produce the first
                    # answer token — capture it now (TTFT point).
                    s.generated.append(int(nxt[i]))
                    if s.ttft_s is None:
                        s.ttft_s = now + dt - s.req.arrival_s
            else:
                s.generated.append(int(nxt[i]))
            if len(s.generated) == 1 and not s.pending:
                mark("first_token", req_id=s.req.req_id)
            answered = (not s.pending
                        and len(s.generated) >= s.req.max_new_tokens)
            out_of_capacity = s.write_slot >= self.capacity
            if answered or out_of_capacity:
                done.append(ScheduledResult(
                    s.req.req_id, s.req.context_key,
                    s.ttft_s if s.ttft_s is not None else now + dt -
                    s.req.arrival_s,
                    now + dt, list(s.generated),
                    truncated=out_of_capacity and not answered))
                self.slots[i] = SlotState()
        return done, dt


# ---------------------------------------------------------------------------
# Discrete-event core
# ---------------------------------------------------------------------------

# Event kinds, in tie-break priority order at equal timestamps: completions
# land before arrivals so a lane freed at t can absorb a request arriving
# at t, and ticks run last so they see every admission made "at" t.
# Write completions (insert write-back, demotions, prefetch promotions)
# order after ticks: in-flight-write fencing is time-based (``ready_at``),
# so same-timestamp ordering only affects the trace, not results.
# Chunk completions (paged/chunked prefill) sort last: chunk chains are
# driven by compute-channel bookings with strictly positive service
# times, so ties are rare and a lane admitted by a same-time chunk-done
# simply joins the NEXT tick.
EV_LOAD_DONE = 0
EV_PREFILL_DONE = 1
EV_ARRIVAL = 2
EV_TICK = 3
EV_WRITE_DONE = 4
EV_CHUNK_DONE = 5

EVENT_NAMES = {EV_LOAD_DONE: "load_done", EV_PREFILL_DONE: "prefill_done",
               EV_ARRIVAL: "arrival", EV_TICK: "tick",
               EV_WRITE_DONE: "write_done", EV_CHUNK_DONE: "chunk_done"}


class EventLoop:
    """Priority queue of timestamped events with a monotonic sim clock.

    The clock never moves backwards. Scheduling an event in the past
    (``when < now``) raises ``ValueError`` at ``push`` time — handlers
    always stamp completions at ``now + service`` or ``max(now, ...)``,
    so a past-time push is a simulation bug, not a policy choice. The
    ``max(now, when)`` clamp in ``pop`` remains as a second line of
    defense (and ``SimSanitizer.on_pop`` checks it when sanitizing).
    ``max_events`` is the zero-progress livelock guard — the seed
    ``run_continuous`` could spin forever re-reading a past arrival
    without advancing time; here any handler that keeps scheduling
    same-time work trips the guard with a clear error instead of
    hanging the process.
    """

    def __init__(self, max_events: int = 2_000_000):
        self._heap: List[Tuple[float, int, int, Any]] = []
        self._seq = itertools.count()
        self.now = 0.0
        self.max_events = max_events
        self.processed = 0
        # optional repro.serving.sanitizer.SimSanitizer (read-only hooks)
        self.sanitizer = None

    def push(self, when: float, kind: int, payload: Any = None) -> None:
        if when < self.now:
            raise ValueError(
                f"cannot schedule '{EVENT_NAMES.get(kind, kind)}' at "
                f"t={when:.9f}: simulated clock is already at "
                f"t={self.now:.9f}")
        heapq.heappush(self._heap, (when, kind, next(self._seq), payload))

    def __bool__(self) -> bool:
        return bool(self._heap)

    def pop(self) -> Tuple[float, int, Any]:
        when, kind, _, payload = heapq.heappop(self._heap)
        if self.sanitizer is not None:
            self.sanitizer.on_pop(self.now, when, kind)
        self.now = max(self.now, when)      # monotonic sim clock
        self.processed += 1
        if self.processed > self.max_events:
            raise RuntimeError(
                f"event loop exceeded {self.max_events} events at "
                f"t={self.now:.3f} — zero-progress livelock?")
        return self.now, kind, payload


class LaneSet:
    """Lane bookkeeping shared by the engine's replicas and the
    ``run_continuous`` harness: requests waiting for a lane, lanes
    reserved by in-flight loads, and the single decode-tick chain per
    batcher (with the zero-progress guard)."""

    def __init__(self, batcher: ContinuousBatcher):
        if batcher.n_slots < 1:
            raise ValueError("need at least one lane")
        self.batcher = batcher
        self.waiting: collections.deque = collections.deque()
        self.reserved: set = set()
        self._tick_scheduled = False
        # unified compute (chunked-prefill mode): when set, decode ticks
        # book their service time on this channel — the same one prefill
        # chunks book — so decode and prefill contend for one accelerator
        # instead of running on independent streams. None = legacy
        # dedicated-prefill-stream semantics (bit-identical timing).
        self.compute_chan = None
        self.compute_stats: Optional[Dict[str, float]] = None
        # Sarathi-style per-tick prefill token budget (unified-compute
        # mode only): > 0 holds ready prefill chunks in a priority queue
        # and releases at most ``token_budget`` prefill tokens per
        # decode tick, fused ahead of the decode step — so a prefill
        # storm delays each decode tick by at most the budgeted chunk
        # time instead of the whole backlog. 0 = legacy FIFO interleave
        # (chunks book the channel the moment they are ready).
        self.token_budget = 0
        # heap of (priority, n_new_tokens, t_enqueue, fire) — priority
        # is supplied by the caller (tenant tier, deadline, seq) and
        # fire(now) performs the actual channel booking + event push
        self.chunk_queue: List[Tuple[Any, int, float, Callable]] = []

    def submit_chunk(self, priority, n_new: int, fire: Callable,
                     now: float, loop: Optional[EventLoop] = None) -> None:
        """Budgeted-mode chunk admission: chunks queue in priority order
        and the tick chain drains them within the token budget — armed
        on demand, so even with no decode running the backlog releases
        at paced chunk boundaries instead of dumping onto the channel (a
        lane admitted mid-storm then waits at most ~one budget of chunk
        time, never the whole backlog). Budget off books immediately
        (legacy FIFO interleave)."""
        if self.token_budget <= 0 or loop is None:
            fire(now)
            return
        heapq.heappush(self.chunk_queue, (priority, n_new, now, fire))
        if self.compute_stats is not None:
            self.compute_stats["chunks_deferred"] += 1
        self.ensure_tick(loop, now)

    def _drain_chunks(self, now: float,
                      budget: Optional[int]) -> Optional[float]:
        """Fire queued chunks in priority order; ``budget`` caps the
        released prefill tokens (None = unbounded drain). Returns the
        latest completion time ``fire`` reported, so an idle chain can
        re-arm at the released chunks' boundary."""
        t_last: Optional[float] = None
        while self.chunk_queue:
            if budget is not None and self.chunk_queue[0][1] > budget:
                break
            _, n_new, t_enq, fire = heapq.heappop(self.chunk_queue)
            if budget is not None:
                budget -= n_new
            if self.compute_stats is not None:
                self.compute_stats["defer_wait_s"] += now - t_enq
            end = fire(now)
            if end is not None:
                t_last = end if t_last is None else max(t_last, end)
        return t_last

    def free_lanes(self) -> List[int]:
        return [i for i in self.batcher.free_lanes()
                if i not in self.reserved]

    def occupancy(self) -> int:
        return (len(self.waiting) + len(self.reserved)
                + sum(s.active for s in self.batcher.slots))

    def admit(self, lane: int, req: Request, kv: LaneRun, orig_len: int,
              now: float, kv_frac: float = 1.0) -> None:
        self.reserved.discard(lane)
        self.batcher.admit(lane, req, kv, orig_len, now, kv_frac=kv_frac)

    def issue(self, now: float,
              dispatch: Callable[[int, Request, float], None]) -> None:
        """Reserve free lanes for waiting requests in FIFO order;
        ``dispatch(lane, req, now)`` books the load/prefill and schedules
        the completion event that will ``admit`` into the lane."""
        free = self.free_lanes()
        while free and self.waiting:
            lane, req = free.pop(0), self.waiting.popleft()
            self.reserved.add(lane)
            dispatch(lane, req, now)

    def ensure_tick(self, loop: EventLoop, now: float) -> None:
        if not self._tick_scheduled:
            self._tick_scheduled = True
            loop.push(now, EV_TICK, self)

    def tick(self, loop: EventLoop, now: float
             ) -> Optional[List[ScheduledResult]]:
        """Run one guarded decode tick and chain the next one. Returns
        the finished results, or None when all lanes are idle (the chain
        stops until the next admission re-arms it)."""
        if not any(s.active for s in self.batcher.slots):
            # no decode to protect, but the queue must still make
            # progress or the jobs waiting on chunk completions would
            # deadlock: release one budget's worth and re-arm the chain
            # at the released chunks' boundary, keeping the channel
            # backlog at most one budget deep for any lane admitted
            # mid-drain
            if self.token_budget > 0 and self.chunk_queue:
                t_next = self._drain_chunks(now, self.token_budget)
                if self.chunk_queue and t_next is not None \
                        and t_next > now:
                    loop.push(t_next, EV_TICK, self)
                    return None
            # chunks are clamped to the budget so the paced drain always
            # progresses; an un-paceable leftover (fire with no
            # completion time) falls back to the unbounded dump
            self._drain_chunks(now, None)
            self._tick_scheduled = False
            return None
        if self.compute_chan is not None:
            # budgeted mode: release up to token_budget queued prefill
            # tokens FIRST — they book the channel at ``now``, so the
            # decode step lands right behind exactly the budgeted chunk
            # time (the Sarathi fused step), never the whole backlog
            if self.token_budget > 0:
                self._drain_chunks(now, self.token_budget)
            # unified compute: reserve the decode step on the shared
            # channel first — a prefill chunk already holding it pushes
            # the step (and every result it stamps) past the chunk
            dt = self.batcher.next_dt()
            if dt is None or dt <= 0.0:
                raise RuntimeError("decode tick made no time progress")
            start, end = self.compute_chan.book(now, dt)
            if self.compute_stats is not None and start > now:
                self.compute_stats["ticks_delayed"] += 1
                self.compute_stats["tick_delay_s"] += start - now
                self.compute_stats["tick_delay_max_s"] = max(
                    self.compute_stats.get("tick_delay_max_s", 0.0),
                    start - now)
            done, _ = self.batcher.tick(start)
            loop.push(end, EV_TICK, self)
            return done
        done, dt = self.batcher.tick(now)
        if dt <= 0.0:
            raise RuntimeError("decode tick made no time progress")
        loop.push(now + dt, EV_TICK, self)
        return done


def run_continuous(batcher: ContinuousBatcher, requests: Sequence[Request],
                   load_fn: Callable[[Request, float], Tuple[KVData, int,
                                                             float]],
                   ) -> List[ScheduledResult]:
    """Single-batcher event harness: loads overlap decode.

    load_fn(req, now) -> (kv entry for the context, original token length,
    load/prefill delay seconds) — the AdaptCache lookup/prefill path. The
    load is *issued* when a lane frees up and completes ``load_s`` later;
    decode ticks keep running for already-admitted lanes in the meantime
    (the seed version advanced the global clock by ``load_s``, stalling
    every active lane behind each fetch, and could livelock when idle
    with a past arrival).
    """
    loop = EventLoop()
    lanes = LaneSet(batcher)
    results: List[ScheduledResult] = []
    for req in requests:
        # a workload may stamp arrivals before the clock start; they
        # land immediately (push rejects past-time scheduling outright)
        loop.push(max(loop.now, req.arrival_s), EV_ARRIVAL, req)

    def dispatch(lane: int, req: Request, now: float) -> None:
        kv, orig_len, load_s = load_fn(req, now)
        loop.push(now + load_s, EV_LOAD_DONE, (lane, req, kv, orig_len))

    while loop:
        now, kind, payload = loop.pop()
        if kind == EV_ARRIVAL:
            lanes.waiting.append(payload)
            lanes.issue(now, dispatch)
        elif kind == EV_LOAD_DONE:
            lane, req, kv, orig_len = payload
            lanes.admit(lane, req, kv, orig_len, now)
            lanes.ensure_tick(loop, now)
        elif kind == EV_TICK:
            done = lanes.tick(loop, now)
            if done is not None:
                results.extend(done)
                lanes.issue(now, dispatch)  # freed lanes take new loads
    return results
