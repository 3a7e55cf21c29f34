"""Serving engine: prefill/decode step builders + continuous batching.

The inference side of the framework (paper §5.3.2 evaluates UZIP on vLLM's
prefill-decode disaggregation).  Two deployment modes:

  * **colocated** — one worker runs prefill and decode;
  * **PD-disaggregated** — prefill workers fill KV caches and ship them to
    decode workers over the compressed split-send P2P path
    (serve/kv_transfer.py); decode workers run the batched decode loop.
    ``ServeConfig.pd_disaggregated`` turns the boundary on in-process:
    every admitted request's prefilled cache crosses it through the
    compressed host wire (``pack_cache``/``unpack_cache``), with the codec
    schedule read from a kind-"kv" ``CommPlan`` cached on the cache
    signature — the decision work is paid once, and every subsequent
    admission hits the plan cache (bit-exact, so serving output is
    identical to colocated mode).

``ServeEngine`` implements slot-based continuous batching: a fixed number of
decode slots, each holding one request's cache position; finished slots are
refilled from the queue without stopping the decode loop (static shapes —
the compiled decode step never re-specializes, and the admission cache
signature stays plan-cache-stable).

Weight-sync ingestion (``ingest_weights``): a running engine hot-swaps its
params from a ``sync.WeightSyncEngine`` update stream — full updates apply
unconditionally, XOR-delta updates are version/epoch-fenced against the
engine's current weights (src/repro/sync/, the paper's §5.3.1 workload).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import transformer
from repro.models.config import ArchConfig


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 256
    temperature: float = 0.0  # 0 = greedy
    eos_token: int = -1  # -1 = never stops early
    prefill_chunk: int = 64  # pad prompts to a multiple of this
    # PD-disaggregation boundary: admitted caches cross prefill->decode
    # through the compressed host wire, scheduled by a cached kv CommPlan
    pd_disaggregated: bool = False


def build_prefill_step(cfg: ArchConfig):
    """(params, batch, cache) -> (last logits, filled cache)."""
    def step(params, batch, cache):
        return transformer.prefill(params, batch, cfg, cache)
    return step


def build_decode_step(cfg: ArchConfig):
    """(params, tokens (B,1), cache) -> (logits (B,1,V), cache)."""
    def step(params, tokens, cache, enc_out=None):
        return transformer.decode_step(params, tokens, cache, cfg,
                                       enc_out=enc_out)
    return step


def sample(logits: jax.Array, key, temperature: float):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(
        jnp.int32)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (L,) int32
    max_new: int = 32
    out: Optional[list] = None
    done: bool = False


class ServeEngine:
    """Slot-based continuous batching on a single worker.

    Decode runs over all ``batch_slots`` every step (static shapes); slots
    whose request finished are masked and refilled between steps.  Per-slot
    KV caches live inside one batched cache; admission writes a freshly
    prefilled single-request cache into the slot via indexed updates.
    """

    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig, *,
                 kv_policy=None, kv_plan_cache=None):
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.kv_policy = kv_policy
        self.kv_plan_cache = kv_plan_cache
        self.kv_compressor = None
        if scfg.pd_disaggregated:
            from repro.core.policy import CompressionPolicy
            from repro.p2p.engine import Compressor
            if self.kv_policy is None:
                self.kv_policy = CompressionPolicy(min_bytes=0)
            if self.kv_plan_cache is None:
                from repro import sched
                self.kv_plan_cache = sched.default_cache()
            self.kv_compressor = Compressor(codec_name="packed")
        # KV-wire integrity recovery: re-pack budget per shipment, and a
        # test seam that interposes on the packed wire (chaos injection)
        self._kv_max_tries = 3
        self.kv_fault_injector: Optional[Callable] = None
        self.prefill_step = jax.jit(build_prefill_step(cfg))
        self.decode_step = jax.jit(build_decode_step(cfg))
        self._splice = jax.jit(self._splice_impl, donate_argnums=(0,))
        self.cache = transformer.init_cache(cfg, scfg.batch_slots, scfg.max_len)
        self.tokens = jnp.zeros((scfg.batch_slots, 1), jnp.int32)
        self.slots: list = [None] * scfg.batch_slots
        self.pos = np.zeros(scfg.batch_slots, np.int64)
        self.budget = np.zeros(scfg.batch_slots, np.int64)
        self.queue: list = []
        self.finished: list = []
        self._key = jax.random.PRNGKey(0)
        # weight-sync ingestion state (None until the first ingest): the
        # version/epoch of self.params under the sync protocol
        self.weight_version: Optional[int] = None
        self.weight_epoch: Optional[int] = None

    # -- weight-sync ingestion -----------------------------------------------

    def ingest_weights(self, update) -> int:
        """Hot-swap ``self.params`` from a weight-sync stream.

        ``update`` is a ``sync.SyncUpdate`` (trainer-side
        ``WeightSyncEngine.update_for``).  Full updates apply
        unconditionally and adopt the stream's epoch; delta updates are
        FENCED — they only apply when this engine's (version, epoch)
        matches the update's base exactly, since XOR reconstruction
        against any other bits would be garbage.  A fencing violation
        raises (the sender consults acks, so it means a protocol bug or a
        lost ack — the caller should re-request a full send).  Decode
        shapes are unchanged, so the jitted prefill/decode steps never
        re-specialize.  Returns the new version.

        Integrity: updates carrying a checksum are verified BEFORE the
        fence or any apply — a corrupt payload raises
        ``WireIntegrityError`` (counted under
        ``serve_ingest_rejects_total{reason="checksum"}``) and the
        engine's weights are untouched; the sender should re-send,
        escalating delta -> full -> raw (``sync/fleet.py``)."""
        from repro.core.integrity import WireIntegrityError
        from repro.sync.engine import apply_update, verify_update

        with obs.span("serve:ingest", version=update.version):
            if update.checksum is not None:
                with obs.span("serve:verify"):
                    intact = verify_update(update)
                if not intact:
                    obs.metric("serve_ingest_rejects_total").inc(
                        reason="checksum")
                    raise WireIntegrityError(
                        f"update v{update.version} failed its payload "
                        f"checksum; re-send it (escalate delta -> full -> "
                        f"raw)")
            if update.base_version is not None:
                if (update.base_version != self.weight_version
                        or update.epoch != self.weight_epoch):
                    obs.metric("serve_ingest_rejects_total").inc(
                        reason="fence")
                    raise ValueError(
                        f"delta update v{update.version} assumes base "
                        f"v{update.base_version}@e{update.epoch} but this "
                        f"engine holds v{self.weight_version}"
                        f"@e{self.weight_epoch}; request a full send")
                with obs.span("sync:apply", mode="delta"):
                    self.params = apply_update(update,
                                               base_params=self.params)
            else:
                with obs.span("sync:apply", mode="full"):
                    self.params = apply_update(update)
            self.weight_version = update.version
            self.weight_epoch = update.epoch
        return self.weight_version

    # -- admission -----------------------------------------------------------

    def submit(self, req: Request):
        req.out = []
        self.queue.append(req)
        obs.metric("serve_queue_depth").set(len(self.queue))

    @staticmethod
    def _splice_impl(batched_cache, one_cache, slot):
        """Write a single-request cache (batch=1) into slot ``slot``."""
        def leafwise(b, o):
            if b.ndim == 0:
                return b
            # batch dim: prefix/blocks caches have batch at 0 or 1 (stacked)
            if o.shape[0] == 1 and b.shape[: 1] != o.shape[: 1]:
                return jax.lax.dynamic_update_slice_in_dim(b, o.astype(b.dtype), slot, 0)
            if o.ndim >= 2 and o.shape[1] == 1:
                return jax.lax.dynamic_update_slice_in_dim(b, o.astype(b.dtype), slot, 1)
            return b
        # "pos" is scalar-per-engine; slot positions tracked host-side
        out = {}
        for k, v in batched_cache.items():
            if k == "pos":
                out[k] = v
                continue
            out[k] = jax.tree.map(leafwise, v, one_cache[k])
        return out

    def _admit(self):
        admitted = 0
        for s in range(self.scfg.batch_slots):
            if self.slots[s] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            admitted += 1
            with obs.span("serve:admit", rid=req.rid, slot=s):
                pad = -len(req.prompt) % self.scfg.prefill_chunk or 0
                toks = np.concatenate([np.zeros(pad, np.int32), req.prompt])
                one_cache = transformer.init_cache(self.cfg, 1,
                                                   self.scfg.max_len)
                with obs.span("serve:prefill", tokens=len(toks)):
                    logits, one_cache = self.prefill_step(
                        self.params, {"tokens": jnp.asarray(toks[None])},
                        one_cache)
                if self.scfg.pd_disaggregated:
                    one_cache = self._ship_kv(one_cache)
                # NOTE: left-padding shifts positions; acceptable for the demo
                # engine (pad=0 when prompts align with prefill_chunk)
                nxt = sample(logits[:, -1], self._next_key(),
                             self.scfg.temperature)
                self.cache = self._splice(self.cache, one_cache, s)
                self.tokens = self.tokens.at[s, 0].set(nxt[0])
                req.out.append(int(nxt[0]))
                if req.max_new <= 1:  # prefill-sampled token was the budget
                    req.done = True
                    self.finished.append(req)
                    continue
                self.slots[s] = req
                self.pos[s] = len(toks)
                self.budget[s] = req.max_new - 1  # 1st token from prefill
        if admitted:
            obs.metric("serve_admitted_total").inc(admitted)
        obs.metric("serve_queue_depth").set(len(self.queue))
        obs.metric("serve_active_slots").set(
            sum(r is not None for r in self.slots))

    def _ship_kv(self, one_cache):
        """Cross the prefill->decode boundary: pack the freshly prefilled
        cache with the host compressor and unpack it on the decode side.

        The codec schedule comes from a kind-"kv" CommPlan keyed on the
        cache signature (``kv_transfer.ship_cache``): the first admission
        compiles it, every later admission of the same-shaped cache is a
        plan-cache hit — zero re-derived decisions per request.  The wire
        is bit-exact, so PD-disaggregated serving emits exactly the tokens
        colocated serving would.

        Integrity: the wire carries a checksum (``pack_cache``) that
        ``unpack_cache`` verifies before decoding; on mismatch the
        shipment is re-packed from the still-held prefill cache — a
        bounded retry (``_kv_max_tries``) counted under
        ``serve_kv_retries_total``.  ``kv_fault_injector`` (None outside
        tests) interposes on the wire between pack and unpack — the
        chaos hook for corrupting shipments in flight."""
        from repro.core.integrity import WireIntegrityError
        from repro.serve.kv_transfer import ship_cache, unpack_cache

        with obs.span("serve:kv_ship"):
            last_err = None
            for _ in range(max(self._kv_max_tries, 1)):
                wire = ship_cache(one_cache, self.kv_compressor,
                                  policy=self.kv_policy,
                                  plan_cache=self.kv_plan_cache)[0]
                if self.kv_fault_injector is not None:
                    wire = self.kv_fault_injector(wire)
                try:
                    out = unpack_cache(wire, self.kv_compressor)
                except WireIntegrityError as e:
                    last_err = e
                    obs.metric("serve_kv_retries_total").inc()
                    continue
                return out
            raise WireIntegrityError(
                f"KV shipment failed integrity {self._kv_max_tries} times"
            ) from last_err

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    # -- decode loop -----------------------------------------------------------

    def step(self):
        """One batched decode step over all active slots."""
        if all(s is None for s in self.slots):
            self._admit()
            if all(s is None for s in self.slots):
                return False
        # engine-wide cache pos = max slot pos (slot caches padded before it)
        active = sum(r is not None for r in self.slots)
        with obs.span("serve:decode_step", active=active):
            self.cache["pos"] = jnp.asarray(int(self.pos.max()), jnp.int32)
            logits, self.cache = self.decode_step(self.params, self.tokens,
                                                  self.cache)
            nxt = sample(logits[:, -1], self._next_key(),
                         self.scfg.temperature)
            self.tokens = nxt[:, None]
            produced = 0
            for s, req in enumerate(self.slots):
                if req is None:
                    continue
                t = int(nxt[s])
                req.out.append(t)
                produced += 1
                self.pos[s] += 1
                self.budget[s] -= 1
                if self.budget[s] <= 0 or t == self.scfg.eos_token or \
                   self.pos[s] >= self.scfg.max_len - 1:
                    req.done = True
                    self.finished.append(req)
                    self.slots[s] = None
        obs.metric("serve_decode_steps_total").inc()
        obs.metric("serve_tokens_total").inc(produced)
        obs.metric("serve_tokens_per_step").set(produced)
        self._admit()
        return True

    def run(self, max_steps: int = 10_000):
        steps = 0
        while steps < max_steps and (self.queue or any(
                s is not None for s in self.slots)):
            if not self.step():
                break
            steps += 1
        return self.finished
