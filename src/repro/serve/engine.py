"""Batched serving engine: slot-based continuous batching over the
model's prefill/decode steps, with a fully device-resident hot path.

Requests are admitted into fixed decode slots (static shapes — TPU
friendly); each engine step decodes one token for every active slot.
Finished slots (EOS or max_tokens) are refilled from the queue.

Device-resident decode loop:
  * sampling (greedy + temperature/top-k via the JAX PRNG) is fused into
    the jitted decode step, so only (slots,) token ids and done-flags —
    never the (slots, vocab) logits — cross to host each token;
  * the decode state (KV caches / SSM states) plus the per-slot
    ``last_token``/``positions`` arrays are donated to the step
    (``donate_argnums``), so they are updated in place instead of copied;
  * admission inserts prefilled rows with one jitted, donated slot-insert
    (a masked gather) instead of a per-leaf host-side ``at[:, slot].set``;
  * prefill pads prompts to power-of-two buckets (capped at ``cache_len``)
    and runs one batched prefill per bucket, so the prefill jit cache is
    bounded by the number of buckets instead of growing per distinct
    prompt length.

The only per-token host work is bookkeeping of finished requests.
Each step's phases (admission, prefill per bucket, decode dispatch, the
token readback and the per-slot bookkeeping) are host spans
(:mod:`repro.obs`) in a profiler's trace.
``submit`` validates prompts: empty prompts and prompts that cannot fit
the cache (``len(prompt) >= cache_len``) raise ``ValueError`` instead of
silently truncating.

Per-request service timing (submit/admit/first-token/done timestamps,
derived TTFT / TPOT / queue-wait) is recorded against the engine's
clock; a request is admitted when it is given a slot, before its
prefill.  ``engine.stats`` doubles as the raw counter dict (mapping
access) and, when *called*, returns a summary with latency percentiles —
the shape campaign ``RunReport`` aggregation expects.  Its counters
include the real and the padded prompt tokens prefilled
(``prefill_tokens``, ``prefill_padded_tokens``: slots x bucket per
call) and the live slots summed over decode steps
(``decode_active_slots``).

:class:`repro.serve.scheduler.ServeScheduler` builds continuous-batching
admission (arrival process, SLO shedding, paged-KV eviction, streaming)
on top of the ``_select_admissions`` / ``_fill_slots`` / ``_retire``
hooks this class exposes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import (decode_and_sample, init_decode_state,
                          prefill_and_sample)

# Request lifecycle states
QUEUED = "queued"        # submitted, waiting for a slot
RUNNING = "running"      # occupying a decode slot
DONE = "done"            # retired normally (EOS / max_tokens / cache bound)
SHED = "shed"            # dropped by SLO admission before getting a slot


class Clock:
    """Wall clock; swappable for a :class:`VirtualClock` in tests/benches."""

    def now(self) -> float:
        return time.perf_counter()

    def sleep_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)

    def on_step(self) -> None:     # virtual clocks advance per decode step
        pass


class VirtualClock(Clock):
    """Deterministic clock: time moves only when told to.  ``dt_per_step``
    makes every decode step cost a fixed amount of virtual time, so
    queue-wait / deadline behaviour is reproducible in tests."""

    def __init__(self, start: float = 0.0, dt_per_step: float = 0.0):
        self.t = float(start)
        self.dt_per_step = float(dt_per_step)

    def now(self) -> float:
        return self.t

    def sleep_until(self, t: float) -> None:
        self.t = max(self.t, float(t))

    def advance(self, dt: float) -> None:
        self.t += float(dt)

    def on_step(self) -> None:
        self.t += self.dt_per_step


@dataclasses.dataclass(eq=False)   # identity equality: prompts are arrays
class Request:
    rid: int
    prompt: np.ndarray                  # (P,) int32
    max_tokens: int = 16
    eos_id: Optional[int] = None
    # per-request sampling knobs: temperature <= 0 decodes greedily
    # (subject to the engine-level ``greedy`` default); top_k == 0 samples
    # the full vocab.
    temperature: float = 0.0
    top_k: int = 0
    # scheduling knobs (JobSpec.priority semantics: higher runs first;
    # deadline_ms is a TTFT SLO measured from submit time — the scheduler
    # sheds requests that can no longer meet it)
    priority: int = 0
    deadline_ms: Optional[float] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = QUEUED
    evictions: int = 0
    # streaming: called as on_token(request, token_id, finished) from the
    # host bookkeeping loop the moment each token id reaches the host
    on_token: Optional[Callable[["Request", int, bool], None]] = \
        dataclasses.field(default=None, repr=False, compare=False)
    # service timestamps (engine-clock seconds; filled by the engine)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None

    # ------------------------------------------------- derived latencies
    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token (submit -> first token on host)."""
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot_s(self) -> Optional[float]:
        """Time per output token over the decode phase."""
        if self.t_done is None or self.t_first is None:
            return None
        return ((self.t_done - self.t_first)
                / max(1, len(self.generated) - 1))

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_admit is None or self.t_submit is None:
            return None
        return self.t_admit - self.t_submit

    def met_deadline(self) -> bool:
        """Did the first token arrive within the TTFT SLO?"""
        if self.status != DONE:
            return False
        if self.deadline_ms is None:
            return True
        ttft = self.ttft_s
        return ttft is not None and ttft * 1e3 <= self.deadline_ms


def validate_request(req: Request, cache_len: int) -> None:
    """Reject prompts the engine cannot serve faithfully: empty prompts
    have no token to prefill from; prompts >= cache_len would silently
    lose their head to the ring buffer."""
    plen = len(req.prompt)
    if plen == 0:
        raise ValueError(f"request {req.rid}: empty prompt — a request "
                         f"needs at least one prompt token")
    if plen >= cache_len:
        raise ValueError(
            f"request {req.rid}: prompt length {plen} >= cache_len "
            f"{cache_len}; the cache holds at most cache_len - 1 prompt "
            f"tokens plus one generated token — shorten the prompt or "
            f"serve with a larger cache_len")


class EngineStats(dict):
    """The engine's raw counters (plain mapping access, e.g.
    ``stats["decode_steps"]``) that is also *callable*: ``stats()``
    returns a summary dict with per-request latency percentiles."""

    def __init__(self, engine: "ServeEngine", **counters):
        super().__init__(**counters)
        self._engine = engine

    def __call__(self) -> Dict[str, object]:
        return self._engine._stats_summary()


def _pctl(values: List[float], q: float) -> Optional[float]:
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    return round(float(np.percentile(np.asarray(vals, np.float64), q)), 6)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 cache_len: int = 256, greedy: bool = True, seed: int = 0,
                 min_bucket: int = 8, clock: Optional[Clock] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.greedy = greedy
        self.min_bucket = min_bucket
        self.clock = clock or Clock()

        self.state = init_decode_state(cfg, slots, cache_len)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.completed: List[Request] = []

        # device-resident per-slot decode inputs (never pulled per token)
        self.last_token = jnp.zeros((slots,), jnp.int32)
        self.positions = jnp.zeros((slots,), jnp.int32)
        self._temps = jnp.zeros((slots,), jnp.float32)
        self._topks = jnp.zeros((slots,), jnp.int32)
        self._eos = jnp.full((slots,), -1, jnp.int32)
        # host bookkeeping mirror of positions (advanced analytically — no
        # device readback)
        self._host_pos = np.zeros(slots, np.int64)

        self._base_key = jax.random.PRNGKey(seed)
        self._tick = 0
        self._decode_traces = 0
        self.stats = EngineStats(
            self, decode_steps=0, host_transfer_bytes=0, prefill_calls=0,
            admitted=0, prefill_tokens=0, prefill_padded_tokens=0,
            decode_active_slots=0)

        def fused_decode(p, state, last_tok, pos, base_key, tick,
                         temps, topks, eos, sampling):
            # Python body runs only while jax traces (i.e. compiles) a new
            # program — this counter is therefore the decode compile count
            self._decode_traces += 1
            key = jax.random.fold_in(base_key, tick)
            tok, new_state = decode_and_sample(
                p, cfg, state, last_tok[:, None], pos, key, temps, topks,
                greedy_only=not sampling)
            return new_state, tok, pos + 1, tok == eos

        # `sampling` is static: the all-greedy decode program (the common
        # case) skips the full-vocab sort + categorical draw; at most two
        # programs are ever traced
        self._decode = jax.jit(fused_decode, donate_argnums=(1, 2, 3),
                               static_argnums=(9,))
        self._needs_sampling = False

        def slot_insert(state, pstate, last_tok, pos, src_row, ptoks, plens):
            """Scatter prefilled rows into engine slots: slot s takes
            prefill row src_row[s] (or keeps its state if src_row[s] < 0)."""
            take = src_row >= 0
            row = jnp.maximum(src_row, 0)

            def put(e, n):
                g = jnp.take(n, row, axis=1)
                m = take.reshape((1, -1) + (1,) * (e.ndim - 2))
                return jnp.where(m, g.astype(e.dtype), e)

            new_state = jax.tree.map(put, state, pstate)
            last = jnp.where(take, jnp.take(ptoks, row), last_tok)
            newpos = jnp.where(take, jnp.take(plens, row), pos)
            return new_state, last, newpos

        self._insert = jax.jit(slot_insert, donate_argnums=(0, 1, 2, 3))
        self._prefill_cache: Dict[int, object] = {}

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        validate_request(req, self.cache_len)
        if req.t_submit is None:
            req.t_submit = self.clock.now()
        req.status = QUEUED
        self.queue.append(req)

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill programs traced so far (≤ bucket count)."""
        return len(self._prefill_cache)

    @property
    def decode_compiles(self) -> int:
        """Distinct decode programs traced so far (≤ 2: greedy-only and
        sampling variants).  Flat after warmup — continuous admission
        must never retrace the decode step."""
        return self._decode_traces

    def bucket(self, plen: int) -> int:
        """Power-of-two pad target for a prompt length, ≥ min_bucket and
        capped at cache_len (the longest admissible prompt)."""
        b = max(self.min_bucket, 1 << max(0, plen - 1).bit_length())
        return min(b, self.cache_len)

    def n_buckets(self) -> int:
        """Upper bound on distinct prefill programs this engine can trace."""
        return len({self.bucket(p) for p in range(1, self.cache_len)})

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            cfg, cache_len = self.cfg, self.cache_len

            @jax.jit
            def fn(params, toks, lengths, base_key, tick, temps, topks):
                key = jax.random.fold_in(base_key, tick)
                return prefill_and_sample(
                    params, cfg, {"tokens": toks}, cache_len=cache_len,
                    key=key, temperature=temps, top_k=topks, lengths=lengths)
            self._prefill_cache[bucket] = fn
        return self._prefill_cache[bucket]

    def _effective_sampling(self, req: Request):
        temp = float(req.temperature)
        if temp <= 0.0 and not self.greedy:
            temp = 1.0
        return temp, int(req.top_k)

    # --------------------------------------------------- admission hooks
    def _prompt_tokens(self, req: Request) -> np.ndarray:
        """Tokens to prefill for an admitted request.  The scheduler
        overrides this to re-prefill prompt+generated on eviction resume."""
        return np.asarray(req.prompt)

    def _select_admissions(self) -> List:
        """Admission policy: (slot, request) pairs to admit this tick.
        Base engine: FIFO into free slots.  The scheduler overrides this
        with priority order, SLO shedding and paged-KV budgeting."""
        free = [s for s in range(self.slots) if self.active[s] is None]
        pairs = []
        while free and self.queue:
            pairs.append((free.pop(0), self.queue.pop(0)))
        return pairs

    def _admit(self):
        with obs.span(obs.SERVE_ADMIT) as sp:
            admitted = self._select_admissions()
            sp.set_metadata(admitted=len(admitted), queued=len(self.queue))
        if not admitted:
            return
        now = self.clock.now()
        for _, req in admitted:
            if req.t_admit is None:
                req.t_admit = now
        self._fill_slots(admitted)
        self._sync_slot_meta()

    def _fill_slots(self, admitted: List):
        """Prefill + insert the selected (slot, request) pairs, grouped by
        pad bucket so the prefill jit cache stays bounded."""
        groups: Dict[int, list] = {}
        for slot, req in admitted:
            toks_np = self._prompt_tokens(req)
            plen = min(len(toks_np), self.cache_len - 1)
            groups.setdefault(self.bucket(plen), []).append(
                (slot, req, toks_np, plen))

        for bucket, grp in sorted(groups.items()):
            rows = len(grp)
            tokens = sum(plen for _, _, _, plen in grp)
            with obs.span(obs.SERVE_PREFILL, bucket=bucket, slots=self.slots,
                          rows=rows, tokens=tokens,
                          rids=lambda: " ".join(str(req.rid)
                                                for _, req, _, _ in grp)):
                self._prefill_group(bucket, grp)
            self.stats["prefill_tokens"] += tokens
            self.stats["prefill_padded_tokens"] += self.slots * bucket

    def _prefill_group(self, bucket: int, grp: list):
        """One fixed (slots, bucket) prefill batch — rows beyond the group
        are dummies (length 0, state discarded by the insert mask)."""
        toks = np.zeros((self.slots, bucket), np.int32)
        lens = np.zeros(self.slots, np.int32)
        temps = np.zeros(self.slots, np.float32)
        topks = np.zeros(self.slots, np.int32)
        src_row = np.full(self.slots, -1, np.int32)
        for r, (slot, req, toks_np, plen) in enumerate(grp):
            toks[r, :plen] = toks_np[-plen:]
            lens[r] = plen
            temps[r], topks[r] = self._effective_sampling(req)
            src_row[slot] = r
        self._tick += 1
        ptoks, pstate = self._prefill_fn(bucket)(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            self._base_key, np.int32(self._tick), jnp.asarray(temps),
            jnp.asarray(topks))
        self.state, self.last_token, self.positions = self._insert(
            self.state, pstate, self.last_token, self.positions,
            jnp.asarray(src_row), ptoks, jnp.asarray(lens))
        first = np.asarray(ptoks)          # (slots,) — admit-time only
        self.stats["prefill_calls"] += 1
        now = self.clock.now()
        for r, (slot, req, toks_np, plen) in enumerate(grp):
            self.active[slot] = req
            req.status = RUNNING
            tok = int(first[r])
            req.generated.append(tok)
            if req.t_first is None:
                req.t_first = now
            self._host_pos[slot] = plen
            self.stats["admitted"] += 1
            finished = len(req.generated) >= req.max_tokens
            if finished:
                self._retire(slot, req)
            if req.on_token:
                req.on_token(req, tok, finished)

    def _sync_slot_meta(self):
        """Refresh the per-slot sampling/EOS device arrays (admit-time
        host→device upload; nothing here runs per token)."""
        temps = np.zeros(self.slots, np.float32)
        topks = np.zeros(self.slots, np.int32)
        eos = np.full(self.slots, -1, np.int32)
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            temps[slot], topks[slot] = self._effective_sampling(req)
            if req.eos_id is not None:
                eos[slot] = req.eos_id
        self._temps = jnp.asarray(temps)
        self._topks = jnp.asarray(topks)
        self._eos = jnp.asarray(eos)
        self._needs_sampling = bool((temps > 0.0).any())

    # ------------------------------------------------------- retirement
    def _retire(self, slot: int, req: Request):
        """Free a slot whose request finished normally."""
        req.done = True
        req.status = DONE
        req.t_done = self.clock.now()
        self.completed.append(req)
        self.active[slot] = None

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One decode step across all active slots.  Returns whether a
        decode actually ran (False: nothing active after admission)."""
        with obs.span(obs.SERVE_STEP, tick=self._tick):
            self._admit()
            return self._decode_tick()

    def _decode_tick(self) -> bool:
        """Decode one token for every active slot (no admission)."""
        active = self.slots - self.active.count(None)
        if not active:
            return False
        self._tick += 1
        tick = self._tick
        with obs.span(obs.SERVE_DECODE, tick=tick, active=active,
                      slots=self.slots):
            self.state, tok, self.positions, eos_hit = \
                self._decode(self.params, self.state, self.last_token,
                             self.positions, self._base_key,
                             np.int32(tick), self._temps, self._topks,
                             self._eos, self._needs_sampling)
        self.last_token = tok
        # the ONLY per-token device→host transfer: token ids + done flags;
        # the host waits here for the decode step to end on the device
        with obs.span(obs.SERVE_READBACK, tick=tick):
            tok_h = np.asarray(tok)
            eos_h = np.asarray(eos_hit)
        self.stats["decode_steps"] += 1
        self.stats["decode_active_slots"] += active
        self.stats["host_transfer_bytes"] += tok_h.nbytes + eos_h.nbytes
        self._host_pos += 1
        self.clock.on_step()

        with obs.span(obs.SERVE_EMIT) as sp:
            retired = 0
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                tok_i = int(tok_h[slot])
                req.generated.append(tok_i)
                finished = (bool(eos_h[slot])
                            or len(req.generated) >= req.max_tokens
                            or self._host_pos[slot] >= self.cache_len - 1)
                if finished:
                    self._retire(slot, req)
                    retired += 1
                if req.on_token:
                    req.on_token(req, tok_i, finished)
            if retired:
                self._sync_slot_meta()
            sp.set_metadata(retired=retired)
        return True

    def run(self, max_steps: int = 1000) -> List[Request]:
        for _ in range(max_steps):
            self.step()
            if not self.queue and all(r is None for r in self.active):
                break
        return self.completed

    # ------------------------------------------------------------ stats
    def _stats_extra(self) -> Dict[str, object]:
        """Engine-specific stats()-summary fields (scheduler overrides)."""
        return {}

    def _stats_summary(self) -> Dict[str, object]:
        done = [r for r in self.completed if r.status == DONE]
        ttft = [r.ttft_s for r in done]
        tpot = [r.tpot_s for r in done]
        qwait = [r.queue_wait_s for r in done]
        summary = {
            "completed": len(done),
            "queued": len(self.queue),
            "running": sum(r is not None for r in self.active),
            "decode_steps": self.stats["decode_steps"],
            "prefill_calls": self.stats["prefill_calls"],
            "admitted": self.stats["admitted"],
            "host_transfer_bytes": self.stats["host_transfer_bytes"],
            "prefill_compiles": self.prefill_compiles,
            "decode_compiles": self.decode_compiles,
            "evictions": sum(r.evictions for r in done),
            "ttft_p50_s": _pctl(ttft, 50), "ttft_p99_s": _pctl(ttft, 99),
            "tpot_p50_s": _pctl(tpot, 50), "tpot_p99_s": _pctl(tpot, 99),
            "queue_wait_p50_s": _pctl(qwait, 50),
            "queue_wait_p99_s": _pctl(qwait, 99),
        }
        summary.update(self._stats_extra())
        return summary
