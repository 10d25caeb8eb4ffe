"""Continuous-batching serving engine over the slotted KV cache.

One jitted decode step runs the WHOLE slot plane every tick (`flash_decode` /
reference GQA over per-slot position maps); requests join and leave by
flipping per-slot registers, never by changing a traced shape — the decode
step is traced exactly once per engine (asserted in tests via
`decode_trace_count`). Prompt ingestion is CHUNKED: each admission prefills
`prefill_chunk` tokens per scheduler round, interleaved with decode steps, so
a long prompt cannot starve in-flight decodes and time-to-first-token stays
bounded.

Two scheduling modes share every jitted function:

  * ``continuous`` — admit into any free slot immediately, recycle a slot the
    tick its request completes (the serving path);
  * ``static``     — the lock-step baseline: admit a wave of up to `n_slots`
    requests, prefill them all, decode until the LAST one finishes, then
    recycle the whole wave (what `launch/serve.py` did before this engine).

Time: the engine keeps a VIRTUAL clock advanced by an explicit `CostModel`
(seconds per decode dispatch over the plane, per prefill chunk, per
admission). Latency/throughput numbers are therefore deterministic for a
given trace and directly comparable across modes — the decode dispatch
computes every slot whether or not it is occupied, which is exactly why
occupancy (what continuous batching buys) shows up as throughput.

Sampling: every request gets a dedicated RNG stream folded from the engine
seed and the request id at admission; the token at sequence position p is
sampled with `fold_in(request_stream, p)` INSIDE the jitted step — no key is
ever shared with prompt generation or across requests.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import RetraceSentinel
from repro.configs.base import ModelConfig
from repro.models import api, transformer
from repro.serve import cache as cache_lib


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request. `arrival_s` is on the virtual clock; `region` is
    only meaningful when routed through a `RegionRouter`."""
    rid: int
    prompt: np.ndarray                   # (P,) int32 token ids
    max_new_tokens: int
    region: int = 0
    arrival_s: float = 0.0


@dataclasses.dataclass
class RequestRecord:
    """Per-request lifecycle trace (virtual-clock timestamps)."""
    rid: int
    region: int
    arrival_s: float
    n_prompt: int
    max_new: int
    admit_s: float = 0.0
    first_tok_s: Optional[float] = None
    done_s: Optional[float] = None
    slot: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    tok_times: List[float] = dataclasses.field(default_factory=list)
    # filled by RoutedCluster
    replica: int = -1
    req_hop_s: float = 0.0
    resp_hop_s: float = 0.0
    held_s: float = 0.0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_tok_s is None:
            return None
        return self.first_tok_s + self.resp_hop_s - self.arrival_s

    @property
    def mean_tok_latency_s(self) -> Optional[float]:
        if self.done_s is None or len(self.tokens) < 2:
            return None
        return (self.done_s - self.first_tok_s) / (len(self.tokens) - 1)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Virtual seconds charged per engine dispatch. The decode charge covers
    the FULL slot plane (the dispatch computes every slot regardless of
    occupancy — that is the physical contract of the fixed-shape step), so
    idle slots cost real time: occupancy is throughput."""
    decode_base_s: float = 0.02          # per decode dispatch
    decode_slot_s: float = 0.002         # x n_slots, occupied or not
    prefill_base_s: float = 0.01         # per prefill-chunk dispatch
    prefill_token_s: float = 0.001       # x chunk width (padded chunk computed)
    admit_s: float = 0.0005              # per admission transition

    def decode_cost(self, n_slots: int) -> float:
        return self.decode_base_s + self.decode_slot_s * n_slots

    def prefill_cost(self, chunk: int) -> float:
        return self.prefill_base_s + self.prefill_token_s * chunk


class ServeEngine:
    """Continuous-batching (or lock-step baseline) serving over one model
    replica. See module docstring for the scheduling/time model."""

    MODES = ("continuous", "static")

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 cache_len: int = 128, max_prompt: int = 64,
                 prefill_chunk: int = 16, mode: str = "continuous",
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None, attn_impl: str = "auto",
                 cost: Optional[CostModel] = None,
                 prefill_chunks_per_tick: int = 2):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"ServeEngine supports transformer decode (dense/moe), got "
                f"family {cfg.family!r}; use the legacy lock-step path in "
                f"launch/serve.py for SSM/hybrid archs")
        if mode not in self.MODES:
            raise ValueError(f"unknown mode {mode!r}; options: {self.MODES}")
        attn_impl = api.resolve_attn_impl(attn_impl)
        self.cfg = cfg
        self.params = params
        self.n_slots = int(n_slots)
        self.cache_len = int(cache_len)
        self.max_prompt = int(max_prompt)
        self.prefill_chunk = int(prefill_chunk)
        self.mode = mode
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = eos_id
        self.attn_impl = attn_impl
        self.cost = cost or CostModel()
        self.prefill_chunks_per_tick = int(prefill_chunks_per_tick)
        self.window = cfg.attn_window

        self.state = cache_lib.init_slot_state(cfg, self.n_slots,
                                               self.cache_len, self.max_prompt,
                                               self.prefill_chunk)
        self.slots = cache_lib.SlotManager(self.n_slots)
        self.queue: Deque[Request] = collections.deque()
        self.records: Dict[int, RequestRecord] = {}      # rid -> record
        self.by_slot: Dict[int, RequestRecord] = {}      # occupied slot -> rec
        self.completed: List[RequestRecord] = []
        self.clock = 0.0
        self.n_decode_dispatches = 0
        self.n_prefill_dispatches = 0
        self._wave: List[int] = []                       # static mode slots
        self._build_fns()

    # ------------------------------------------------------------ jitted fns

    def _build_fns(self):
        cfg, Pc = self.cfg, self.prefill_chunk
        window, attn_impl = self.window, self.attn_impl
        temp, eos = self.temperature, self.eos_id
        base_key = jax.random.PRNGKey(self.seed)
        cache_keys = ("k", "v", "kv_pos", "pos")

        def sample(logits, key):
            if temp <= 0.0:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            return jax.random.categorical(key, logits / temp).astype(jnp.int32)

        def eos_hit(tok):
            if eos is None:
                return jnp.zeros(tok.shape, bool)
            return tok == eos

        def admit(state, slot, prompt, plen, glimit, rid):
            # the request's dedicated sampling stream: engine seed x request
            # id — never the key that generated the prompt, never shared
            req_key = jax.random.fold_in(base_key, rid)
            return cache_lib.reset_slot(state, slot, prompt, plen, glimit,
                                        req_key)

        def prefill(params, state, slot):
            start = state["prefilled"][slot]
            plen = state["prompt_len"][slot]
            n_valid = jnp.minimum(plen - start, Pc)
            chunk = jax.lax.dynamic_slice(state["prompt"], (slot, start),
                                          (1, Pc))[0]
            kv = {k: state[k] for k in cache_keys}
            logits, kv = transformer.prefill_chunk_slotted(
                cfg, params, kv, chunk, slot, start, n_valid, window=window)
            done = (start + n_valid) >= plen
            # token at sequence position p samples fold_in(stream, p); the
            # first generated token sits at position plen
            key = jax.random.fold_in(state["rng"][slot], start + n_valid)
            tok = jnp.where(done, sample(logits, key), state["last_tok"][slot])
            glimit = state["gen_limit"][slot]
            finished = done & ((glimit <= 1) | eos_hit(tok))
            new = {**state, **kv}
            new["prefilled"] = state["prefilled"].at[slot].set(start + n_valid)
            new["active"] = state["active"].at[slot].set(done & ~finished)
            new["last_tok"] = state["last_tok"].at[slot].set(tok)
            new["gen_count"] = state["gen_count"].at[slot].set(
                done.astype(jnp.int32))
            return new, tok

        def decode(params, state):
            active = state["active"]
            pos0 = state["pos"]
            kv = {k: state[k] for k in cache_keys}
            logits, kv = transformer.decode_step_slotted(
                cfg, params, kv, state["last_tok"], active=active,
                window=window, attn_impl=attn_impl)
            # generated token's sequence position is pos0 + 1 (its input, the
            # previous token, is written at pos0) — so streams never collide
            # with the first token's fold_in(stream, plen)
            keys = jax.vmap(jax.random.fold_in)(state["rng"], pos0 + 1)
            toks = jax.vmap(sample)(logits, keys)
            toks = jnp.where(active, toks, state["last_tok"])
            gen_count = state["gen_count"] + active.astype(jnp.int32)
            finished = active & ((gen_count >= state["gen_limit"])
                                 | eos_hit(toks))
            new = {**state, **kv, "last_tok": toks, "gen_count": gen_count,
                   "active": active & ~finished}
            return new, toks, finished

        # trace-once is ENFORCED per engine, not just asserted in tests: the
        # shared RetraceSentinel (repro.analysis.retrace) fails the exact
        # call whose input churned a traced shape/dtype, for all three steps
        donate = () if jax.default_backend() == "cpu" else (0,)
        donate1 = () if jax.default_backend() == "cpu" else (1,)
        self._admit_fn = RetraceSentinel(
            jax.jit(admit, donate_argnums=donate), name="serve.admit")
        self._prefill_fn = RetraceSentinel(
            jax.jit(prefill, donate_argnums=donate1), name="serve.prefill")
        self._decode_fn = RetraceSentinel(
            jax.jit(decode, donate_argnums=donate1), name="serve.decode")

    def decode_trace_count(self) -> int:
        """Number of distinct traces the decode step has compiled — the
        zero-recompile contract says this stays 1 across any batch churn."""
        return self._decode_fn.trace_count

    def prefill_trace_count(self) -> int:
        return self._prefill_fn.trace_count

    # --------------------------------------------------------------- intake

    def submit(self, req: Request) -> None:
        """Queue a request (validates it fits the slot plane)."""
        P = int(np.asarray(req.prompt).shape[0])
        if P < 1 or P > self.max_prompt:
            raise ValueError(f"prompt length {P} outside [1, {self.max_prompt}]")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.window is None and P + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request needs {P + req.max_new_tokens} cache positions > "
                f"cache_len {self.cache_len} (no sliding window to wrap into)")
        if req.rid in self.records:
            raise ValueError(f"duplicate request id {req.rid}")
        self.records[req.rid] = RequestRecord(
            rid=req.rid, region=req.region, arrival_s=req.arrival_s,
            n_prompt=P, max_new=req.max_new_tokens)
        self.queue.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.slots.owner)

    # ------------------------------------------------------------ scheduling

    def _admit_one(self, req: Request) -> None:
        slot = self.slots.acquire(req.rid)
        assert slot is not None
        rec = self.records[req.rid]
        rec.slot, rec.admit_s = slot, self.clock
        self.by_slot[slot] = rec
        P = int(np.asarray(req.prompt).shape[0])
        buf = np.zeros((self.max_prompt + self.prefill_chunk,), np.int32)
        buf[:P] = np.asarray(req.prompt, np.int32)
        # int() coercions: an np-int scalar here would trace a distinct dtype
        # and trip the admit sentinel — the guard's first real catch
        self.state = self._admit_fn(self.state, slot, buf, P,
                                    int(req.max_new_tokens), int(req.rid))
        self.clock += self.cost.admit_s

    def _prefill_one(self, rec: RequestRecord) -> None:
        self.state, tok = self._prefill_fn(self.params, self.state, rec.slot)
        self.n_prefill_dispatches += 1
        self.clock += self.cost.prefill_cost(self.prefill_chunk)
        done_now = min(self.prefill_chunk, rec.n_prompt - len_prefilled(rec))
        rec.prefill_host = len_prefilled(rec) + done_now
        if rec.prefill_host >= rec.n_prompt:
            t = int(tok)                                 # host sync: 1st token
            rec.tokens.append(t)
            rec.tok_times.append(self.clock)
            rec.first_tok_s = self.clock
            if rec.max_new <= 1 or (self.eos_id is not None
                                    and t == self.eos_id):
                self._complete(rec)

    def _decode_tick(self) -> None:
        active = [s for s, r in self.by_slot.items()
                  if r.first_tok_s is not None and r.done_s is None]
        self.state, toks, finished = self._decode_fn(self.params, self.state)
        self.n_decode_dispatches += 1
        self.clock += self.cost.decode_cost(self.n_slots)
        self.slots.note_decode_tick(len(active))
        toks = np.asarray(toks)
        finished = np.asarray(finished)
        for slot in active:
            rec = self.by_slot[slot]
            rec.tokens.append(int(toks[slot]))
            rec.tok_times.append(self.clock)
            if finished[slot]:
                self._complete(rec)

    def _complete(self, rec: RequestRecord) -> None:
        rec.done_s = self.clock
        self.completed.append(rec)
        if self.mode == "continuous":
            self.slots.release(rec.slot)
            del self.by_slot[rec.slot]

    def tick(self) -> None:
        """One scheduler round: admissions, prefill chunks, one decode step."""
        if self.mode == "static":
            self._tick_static()
        else:
            self._tick_continuous()

    def _tick_continuous(self) -> None:
        while self.queue and self.slots.n_free:
            self._admit_one(self.queue.popleft())
        budget = self.prefill_chunks_per_tick
        for slot in sorted(self.by_slot):
            if budget == 0:
                break
            rec = self.by_slot[slot]
            if rec.done_s is None and len_prefilled(rec) < rec.n_prompt:
                self._prefill_one(rec)
                budget -= 1
        if any(r.first_tok_s is not None and r.done_s is None
               for r in self.by_slot.values()):
            self._decode_tick()

    def _tick_static(self) -> None:
        if not self._wave and self.queue:
            # admit a wave, then prefill it COMPLETELY before any decode —
            # the lock-step baseline's head-of-line blocking, made explicit
            while self.queue and self.slots.n_free:
                self._admit_one(self.queue.popleft())
            self._wave = sorted(self.by_slot)
            for slot in self._wave:
                rec = self.by_slot[slot]
                while rec.done_s is None and len_prefilled(rec) < rec.n_prompt:
                    self._prefill_one(rec)
            return
        if any(r.done_s is None for r in self.by_slot.values()):
            self._decode_tick()
        if self._wave and all(self.by_slot[s].done_s is not None
                              for s in self._wave):
            for slot in self._wave:
                self.slots.release(slot)
                del self.by_slot[slot]
            self._wave = []

    # -------------------------------------------------------------- driving

    def run_trace(self, requests: List[Request]) -> List[RequestRecord]:
        """Feed a timed trace through the engine on the virtual clock and run
        to completion. Requests are delivered when the clock passes their
        arrival; the clock jumps over idle gaps."""
        reqs = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        i = 0
        t_wall = time.perf_counter()
        while i < len(reqs) or self.has_work:
            while i < len(reqs) and reqs[i].arrival_s <= self.clock:
                self.submit(reqs[i])
                i += 1
            if not self.has_work:
                self.clock = max(self.clock, reqs[i].arrival_s)
                continue
            self.tick()
        self.wall_s = time.perf_counter() - t_wall
        return self.completed

    # ---------------------------------------------------------------- stats

    def stats(self) -> Dict[str, float]:
        """p50/p99 TTFT, per-token latency, sustained throughput, occupancy —
        all on the virtual clock (deterministic for a given trace)."""
        recs = [r for r in self.completed if r.first_tok_s is not None]
        if not recs:
            return {"completed": 0}
        ttft = np.array([r.ttft_s for r in recs])
        tok_lat = np.array([r.mean_tok_latency_s for r in recs
                            if r.mean_tok_latency_s is not None])
        total_tokens = sum(len(r.tokens) for r in recs)
        t0 = min(r.arrival_s for r in recs)
        t1 = max(r.done_s for r in recs)
        makespan = max(t1 - t0, 1e-9)
        return {
            "completed": len(recs),
            "total_tokens": total_tokens,
            "makespan_s": makespan,
            "tok_per_s": total_tokens / makespan,
            "qps": len(recs) / makespan,
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p99_s": float(np.percentile(ttft, 99)),
            "tok_latency_mean_s": float(tok_lat.mean()) if tok_lat.size else 0.0,
            "tok_latency_p99_s": (float(np.percentile(tok_lat, 99))
                                  if tok_lat.size else 0.0),
            "occupancy": self.slots.mean_occupancy,
            "decode_dispatches": self.n_decode_dispatches,
            "prefill_dispatches": self.n_prefill_dispatches,
            "wall_s": getattr(self, "wall_s", 0.0),
        }


def len_prefilled(rec: RequestRecord) -> int:
    """Host mirror of the device `prefilled` counter (no sync needed: chunk
    size and prompt length are host-known)."""
    return getattr(rec, "prefill_host", 0)
