"""Continuous-batching serving engine with interference-aware scheduling.

The paper's findings drive the scheduler:
  * takeaway §4.2 (HOL blocking): a monolithic prefill blocks the decode
    batch for its whole duration — the engine CHUNKS prefills and
    interleaves chunks between decode steps at per-kernel granularity;
  * §5.1 (estimator-driven decisions): each step the engine predicts the
    decode batch's TBT inflation from colocating one more prefill chunk
    (analytic resource profiles through repro.core.estimator) and sizes
    the chunk to keep predicted TBT within the SLO.

Supported families: uniform-attention decoders (dense/moe). The engine
runs the same jitted decode/extend steps the dry-run lowers.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import DeviceModel, KernelProfile, Scenario, solve_scenarios
from repro.core.resources import RESOURCE_AXES
from repro.models import LOCAL_CTX, ParallelContext, build_model
from repro.models import transformer as tfm
from repro.models.layers import rmsnorm, unembed, embed
from repro.serve.kvcache import Sequence, SlotAllocator


_MIN_CHUNK = 16      # smallest prefill chunk the scheduler will schedule


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 512
    prefill_chunk: int = 128          # max chunk; scheduler may shrink it
    tbt_slo_ms: float = 50.0
    mode: str = "interference_aware"  # | "serial" | "fixed_chunk"
    temperature: float = 0.0
    seed: int = 0


@dataclass
class StepEvent:
    kind: str                  # "decode" | "prefill_chunk" |
                               # "degraded" | "recovered"
    t: float
    detail: dict = field(default_factory=dict)


def engine_steps(model, ctx: ParallelContext = LOCAL_CTX):
    """The engine's two jitted device programs, with the KV cache donated:
    ``decode(params, tokens (B,1), cache, pos (B,))`` for the whole slot
    batch and ``extend(params, tokens (1,C), cache, slot, pos0)`` for one
    prefill chunk of one slot.  Both return ``(logits, cache)``."""
    cfg = model.cfg

    def decode(params, tokens, cache, pos_vec):
        return model.decode_step(params, tokens, cache, pos_vec, ctx)

    def extend(params, tokens, cache, slot, pos0):
        x = embed(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
        ck = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
        cv = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
        x, ck, cv = tfm.uniform_stack_extend(
            params["stack"], cfg, x, ck, cv, pos0, ctx=ctx)
        cache = dict(cache,
                     k=jax.lax.dynamic_update_slice_in_dim(
                         cache["k"], ck, slot, axis=1),
                     v=jax.lax.dynamic_update_slice_in_dim(
                         cache["v"], cv, slot, axis=1))
        x = rmsnorm(params["final_ln"], x[:, -1:], cfg.norm_eps)
        return unembed(params["embed"], x), cache

    return (jax.jit(decode, donate_argnums=(2,)),
            jax.jit(extend, donate_argnums=(2,)))


class Engine:
    """``dev`` is the chip the engine prices its chunk decisions for
    (``repro.core.device_model`` maps an attached TPU's kind to it)."""

    def __init__(self, cfg: ModelConfig, params=None, ecfg: EngineConfig = None,
                 ctx: ParallelContext = LOCAL_CTX, *, dev: DeviceModel,
                 key=None):
        assert cfg.family in ("dense", "moe") and cfg.attn.pattern == "global", \
            "engine supports uniform-attention decoders"
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        self.ctx = ctx
        self.dev = dev
        self.model = build_model(cfg)
        key = key if key is not None else jax.random.PRNGKey(self.ecfg.seed)
        self.params = params if params is not None else self.model.init(key)
        self.alloc = SlotAllocator(self.ecfg.max_slots, self.ecfg.max_len)
        # +1 trash position: idle slots in the static decode batch write
        # their (ignored) k/v there instead of corrupting position 0
        self.cache = self.model.init_cache(self.ecfg.max_slots,
                                           self.ecfg.max_len + 1)
        self.waiting: List[Sequence] = []
        self.events: List[StepEvent] = []
        self.metrics: Dict[int, dict] = {}
        self._next_id = 0
        self.degraded = False
        self._decode, self._extend = engine_steps(self.model, ctx)
        # the greedy pick (argmax, the lowest index on ties as np.argmax),
        # jitted per engine: its cache holds this engine's logits shapes
        self._greedy = jax.jit(lambda logits: jnp.argmax(logits, axis=-1))

    def set_degraded(self, flag: bool, reason: str = "") -> None:
        """Fleet hook: the engine's device is oversubscribed (straggling,
        or absorbing migrated work after a fleet failure).  In degraded
        mode the chunk scheduler stops spending headroom on large prefill
        chunks and always takes the minimum-predicted-TBT candidate —
        prefills slow down, decode TBT is protected."""
        if flag != self.degraded:
            self.degraded = flag
            self.events.append(StepEvent(
                "degraded" if flag else "recovered",
                time.perf_counter(), {"reason": reason}))

    # ------------------------------------------------------------- #
    def submit(self, prompt: List[int], max_new: int = 16) -> int:
        seq = Sequence(self._next_id, len(prompt), max_new,
                       tokens=list(prompt), arrival=time.perf_counter())
        self._next_id += 1
        self.waiting.append(seq)
        return seq.seq_id

    # --------------------- interference model --------------------- #
    def _phase_profile(self, name: str, n_tokens: float) -> KernelProfile:
        """Analytic per-call resource vector for one engine phase: weight
        reads dominate decode; matmul FLOPs dominate prefill chunks."""
        n_active = self.cfg.n_active_params()
        flops = 2.0 * n_active * n_tokens
        bytes_ = 2.0 * n_active + 2e5 * n_tokens   # weights + kv traffic
        demand = {r: 0.0 for r in RESOURCE_AXES}
        demand.update(mxu=flops, vpu=flops / 50, issue=flops / 256,
                      hbm=bytes_, l2=bytes_)
        return KernelProfile(name, demand=demand)

    def _pick_chunk(self, seq: Sequence, n_active_decodes: int) -> int:
        """Largest chunk whose colocation keeps predicted decode TBT within
        the SLO (paper §5.1 estimator-in-the-loop). Every halving candidate
        down to and INCLUDING the floor chunk is one `Scenario` (victim =
        the decode batch, background = the chunk), priced in a single
        batched solve: predicted TBT = the decode step inflated by the
        chunk's interference, plus the chunk itself serialized on the core
        it is interleaved with.  When no candidate passes, the fallback is
        estimator-backed too: the priced candidate with the lowest
        predicted TBT.

        Degraded mode (``set_degraded``, driven by the fleet layer when
        this device is oversubscribed): skip the largest-passing search
        and always take the minimum-predicted-TBT candidate — the
        interference budget belongs to the migrated/SLO work, not to
        prefill throughput."""
        with obs.span("serve.price"):
            remaining = seq.prompt_len - seq.pos
            if self.ecfg.mode == "serial":
                return remaining
            if self.ecfg.mode == "fixed_chunk":
                return min(self.ecfg.prefill_chunk, remaining)
            if n_active_decodes == 0:
                boost = 1 if self.degraded else 4
                return min(self.ecfg.prefill_chunk * boost, remaining)
            chunk = min(self.ecfg.prefill_chunk, remaining)
            cands = []
            while chunk > _MIN_CHUNK:
                cands.append(chunk)
                chunk //= 2
            cands.append(max(chunk, _MIN_CHUNK))   # the floor is priced too
            decode = self._phase_profile("decode", max(n_active_decodes, 1))
            chunks = [self._phase_profile(f"prefill{c}", c) for c in cands]
            br = solve_scenarios(
                [Scenario((decode,), (ch,)) for ch in chunks], self.dev)
            tbt_iso = decode.isolated_time(self.dev)
            t_chunk = np.asarray([ch.isolated_time(self.dev)
                                  for ch in chunks])
            tbt_pred = tbt_iso * br.slowdowns[:, 0] + t_chunk
            if self.degraded:
                return cands[int(np.argmin(tbt_pred))]
            ok = tbt_pred <= max(self.ecfg.tbt_slo_ms / 1e3, tbt_iso * 1.5)
            passing = np.flatnonzero(ok)
            if passing.size:
                return cands[passing[0]]
            # nothing keeps TBT within SLO: degrade to the estimator-backed
            # minimum — the priced candidate with the lowest predicted TBT
            # (the old fallback returned an unpriced cands[-1] // 2)
            return cands[int(np.argmin(tbt_pred))]

    # ----------------------------- loop --------------------------- #
    def step(self) -> bool:
        """One scheduler iteration. Returns False when idle.

        Admits waiting sequences, runs at most one prefill chunk and one
        decode step over the whole static slot batch, and picks each new
        token with ``_sample`` over that program's device logits: at
        temperature <= 0 only the ids cross to the host, and the ids are
        read by ``slot``, so the pick sees two shapes whatever the number
        of decoding slots."""
        now = time.perf_counter
        with obs.span("serve.step"):
            # 1) admit waiting sequences into free slots
            with obs.span("serve.admit"):
                while self.waiting and self.alloc.can_admit(self.waiting[0]):
                    seq = self.waiting.pop(0)
                    self.alloc.admit(seq)
                    seq.admit_time = now()
            active = list(self.alloc.active.values())
            prefilling = [s for s in active if s.pos < s.prompt_len]
            decoding = [s for s in active
                        if s.pos >= s.prompt_len and not s.done]
            if not active:
                return False

            # 2) one prefill chunk for the oldest prefilling sequence
            if prefilling:
                seq = prefilling[0]
                chunk = self._pick_chunk(seq, len(decoding))
                tok = np.asarray(seq.tokens[seq.pos:seq.pos + chunk],
                                 np.int32)[None, :]
                if seq.pos == 0:
                    seq.first_chunk_time = now()
                with obs.span("serve.extend"):
                    logits, self.cache = self._extend(
                        self.params, jnp.asarray(tok), self.cache,
                        seq.slot, seq.pos)
                self.events.append(StepEvent(
                    "prefill_chunk", now(),
                    {"seq": seq.seq_id, "chunk": int(tok.shape[1]),
                     "colocated_decodes": len(decoding)}))
                seq.pos += tok.shape[1]
                if seq.pos >= seq.prompt_len:
                    with obs.span("serve.first_token"):
                        seq.tokens.append(int(self._sample(logits)[0, -1]))
                        seq.first_token_time = now()
                        seq.pos += 1
                    self._record_prefill(seq)

            # 3) one decode step for the whole decode batch
            if decoding:
                B = self.ecfg.max_slots
                with obs.span("serve.decode.inputs"):
                    tokens = np.zeros((B, 1), np.int32)
                    pos = np.full((B,), self.ecfg.max_len, np.int32)  # trash
                    for s in decoding:
                        tokens[s.slot, 0] = s.tokens[-1]
                        pos[s.slot] = s.pos - 1  # position of the token fed
                    tokens, pos = jnp.asarray(tokens), jnp.asarray(pos)
                with obs.span("serve.decode"):
                    logits, self.cache = self._decode(
                        self.params, tokens, self.cache, pos)
                with obs.span("serve.decode.wait"):
                    logits.block_until_ready()
                with obs.span("serve.decode.fetch"):
                    ids = self._sample(logits)
                self.events.append(StepEvent(
                    "decode", now(),
                    {"batch": len(decoding),
                     "pick": "device" if self.ecfg.temperature <= 0
                     else "host"}))
                with obs.span("serve.sample"):
                    for s in decoding:
                        s.tokens.append(int(ids[s.slot, 0]))
                        s.pos += 1
                        if s.pos - s.prompt_len >= s.max_new:
                            s.done = True
                            self._finish(s)
            return True

    @staticmethod
    def _record_prefill(seq: Sequence) -> None:
        """A request's way to its first token, under its ``seq`` id:
        submit -> admit -> first chunk dispatched -> first token."""
        sid = seq.seq_id
        obs.record("serve.queue", seq.arrival, seq.admit_time, seq=sid)
        obs.record("serve.prefill_wait", seq.admit_time,
                   seq.first_chunk_time, seq=sid)
        obs.record("serve.prefill", seq.first_chunk_time,
                   seq.first_token_time, seq=sid)

    def _sample(self, logits: jax.Array) -> np.ndarray:
        """The batch picker: device logits ``[..., V]`` to host token ids
        ``[...]``, one per row.  At temperature <= 0 the argmax runs on
        the device and only the int32 ids are copied to the host.  Above
        it the logits are copied and each row is sampled on the host from
        its softmax at that temperature, with a generator seeded from
        ``seed`` for every row."""
        if self.ecfg.temperature <= 0:
            return np.asarray(self._greedy(logits))
        logits = np.asarray(logits)
        rows = logits.reshape(-1, logits.shape[-1])
        ids = np.empty(len(rows), np.int32)
        for i, row in enumerate(rows):
            p = np.exp((row - row.max()) / self.ecfg.temperature)
            p /= p.sum()
            ids[i] = np.random.default_rng(self.ecfg.seed).choice(len(p), p=p)
        return ids.reshape(logits.shape[:-1])

    def _finish(self, seq: Sequence):
        self.metrics[seq.seq_id] = {
            "prompt_len": seq.prompt_len,
            "new_tokens": len(seq.tokens) - seq.prompt_len,
            "ttft_s": (seq.first_token_time or 0) - seq.arrival,
            "output": seq.tokens[seq.prompt_len:],
        }
        self.alloc.release(seq.seq_id)

    def run_until_done(self, max_steps: int = 10_000) -> Dict[int, dict]:
        for _ in range(max_steps):
            if not self.step() and not self.waiting:
                break
        return self.metrics
