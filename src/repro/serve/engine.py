"""Batched serving engine: slot-based continuous batching.

A fixed pool of B decode slots; each slot holds one active request.  New
requests are prefilled into a free slot (per-slot cache splice), decode
advances ALL active slots with one compiled step, finished slots (EOS or
max_tokens) are immediately refilled from the queue — the standard
continuous-batching loop (vLLM-style, without paging) on top of
models.model.{prefill, decode_step}.

The engine owns the *state* (params, slot pool, KV cache, compiled steps);
the *loop* lives in :mod:`repro.serve.scheduler`, which adds arrival times,
admission/backpressure, deadlines, per-token streaming callbacks, seeded
sampling and TTFT/throughput metrics on top of the same internals.
``run()`` is kept as the thin synchronous driver over that scheduler and
decodes exactly the tokens the pre-scheduler loop did.

Compile behavior: decode compiles once; prefill pads prompts to
power-of-two length buckets so a mixed-length request stream compiles
O(log L) variants instead of one per distinct prompt length.  Padding lives
at the END of the prompt (causal attention means real positions never see
it), is zeroed out of the cache at splice time, and first-token logits are
read at the true last-token index — so bucketed and exact prefill emit the
same tokens.  Bucketing is enabled automatically for pure global-attention
decoders; recurrent/SSM/sliding-window stacks fall back to exact-length
prefill (their states integrate the pad tokens).

When ``kan_deploy=True`` every KAN-FFN block executes through the
``repro.runtime`` registry (``kan_backend`` > ``REPRO_KAN_BACKEND`` >
"pallas"), sharing the runtime's plan/compile cache across prefill and
decode.

Attention routes through the runtime attention registry the same way:
``attn_backend`` ("ref" = chunked XLA, "flash" = fused Pallas
flash-attention) resolves at engine build (explicit arg >
``REPRO_ATTN_BACKEND`` > flash-on-TPU/ref-elsewhere) and rides the
compiled prefill/decode closures as a static jit argument — the backend is
part of the compile key, so two engines with different attention backends
never share a stale trace.  With ``kan_deploy=True`` and
``attn_backend="flash"`` every FLOP-heavy op of the decode step (attention
AND both KAN-FFN halves) executes as a fused Pallas kernel.

With ``kv_block_size=`` the per-slot contiguous KV slab is replaced by a
PAGED pool: KV storage is cut into fixed-size blocks (a multiple of the
flash kernel's 8-row KV tile) handed out by a free-list allocator
(:mod:`repro.serve.kvpool`), each slot addresses its tokens through a
block table, and a hash-keyed prefix cache lets requests sharing a prompt
prefix splice the cached blocks in copy-free instead of re-prefilling.
``prefill_chunk=`` additionally stages long prompts: the scheduler
advances one chunk per round, interleaved with pooled decode, so one long
prompt can't stall TTFT for the pool.  Greedy token streams are
bit-identical to the contiguous path: the paged decode step gathers the
block table into exactly the contiguous cache's (B, max_len, ...) view,
and masked softmax lanes contribute exact zeros regardless of stale block
contents (see ``layers.attention_decode``).

With ``mesh=`` the engine serves distributed: params are placed by the
role-based sharding rules, the slot pool / KV cache shard their slot dim
on "data" — the paged pool shards its num_blocks dim there instead — and
every prefill / decode step runs under ``runtime.use_mesh``, so the
KAN-FFN blocks execute on the mesh-sharded fused pipeline (batch on
"data", output channels on "model").  A single-device mesh serves the
same tokens as no mesh at all.

On CPU/smoke configs this is a functional demo; the same engine drives the
decode_32k serve_step that the dry-run lowers at production shapes.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import model as M
from .. import runtime
from ..obs.trace import profile_scope
from .kvpool import KVBlockPool

__all__ = ["Request", "ServeEngine", "prefill_bucketing_supported",
           "paged_kv_supported"]


def prefill_bucketing_supported(cfg: ModelConfig) -> bool:
    """Right-padded prefill is exact only when no layer state integrates the
    pad tokens: pure global-attention decoders qualify (causal masking +
    masked cache splice make padding invisible); sliding-window caches,
    RG-LRU/SSD states, and encoder/VLM prefixes do not."""
    return (
        cfg.encoder_layers == 0
        and cfg.family not in ("audio", "vlm")
        and all(k == "global" for k in cfg.layer_kinds)
    )


def paged_kv_supported(cfg: ModelConfig) -> bool:
    """Paged KV needs every layer's decode state to be a block-structured
    KV cache — the same pure global-attention decoder predicate as prefill
    bucketing (rolling-window / recurrent / encoder state has no pages)."""
    return prefill_bucketing_supported(cfg)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list                 # token ids
    max_new_tokens: int = 32
    eos_id: int = 2
    # scheduling inputs (consumed by repro.serve.scheduler; the defaults
    # reproduce the classic run() semantics — arrive immediately, never
    # expire, greedy decode — so pre-scheduler call sites work unchanged):
    arrival_s: float = 0.0       # offset from scheduler start; 0 = now
    deadline_s: float | None = None  # max queued seconds before expiry
    sampling: Any = None         # SamplingParams, or None for greedy
    # filled by the engine/scheduler:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "pending"      # pending -> queued -> running -> done|expired
    latency_s: float = 0.0       # admission -> last token
    ttft_s: float = 0.0          # arrival -> first token


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, slots: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 kan_deploy: bool = False, kan_backend: str | None = None,
                 attn_backend: str | None = None,
                 prefill_buckets: bool | None = None, mesh=None,
                 kv_block_size: int | None = None,
                 kv_blocks: int | None = None, prefix_cache: bool = True,
                 prefill_chunk: int | None = None,
                 spec_decode: int = 0, draft_spec=None):
        # the drafter refits from the FLOAT weights; capture them before the
        # kan_deploy quantization below swaps the tree for int8 + SH-LUT
        float_params = params
        if kan_deploy:
            # Execute every KAN-FFN block on the paper's quantized datapath:
            # int8 c' + SH-LUT through the repro.runtime executor registry
            # (decode AND prefill steps — the whole serving hot path).
            if cfg.ffn_kind != "kan":
                raise ValueError(
                    "kan_deploy requires a KAN-FFN config (cfg.kan_variant())"
                )
            # validate eagerly so a typo'd backend fails at engine build,
            # not at first admit
            runtime.resolve_backend(kan_backend)
            from ..core.kan_ffn_deploy import quantize_kan_ffn_params_tree

            params = quantize_kan_ffn_params_tree(params, cfg)
        self.mesh = mesh
        if mesh is not None:
            # Distributed serving: params follow the role-based rules
            # (attention/FFN weights on "model" where the axis divides, the
            # quantized KAN bundles ride replicated — the runtime's
            # shard_map distributes their padded pipeline form at execution)
            # and the slot pool / KV cache shard their slot dim on "data",
            # so every decode step advances the pool data-parallel.
            from ..dist.sharding import cache_pspecs, param_pspecs, to_shardings

            params = jax.device_put(
                params, to_shardings(param_pspecs(params, mesh), mesh)
            )
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        self.kan_backend = kan_backend if kan_deploy else None
        # Attention backend ("ref" XLA / "flash" fused Pallas): resolved and
        # validated EAGERLY — a typo fails at engine build, and the resolved
        # name is baked into the compiled prefill/decode closures as a
        # static jit argument, so switching backends retraces instead of
        # silently reusing the other backend's step (plan-cache keying).
        self.attn_backend = runtime.resolve_attn_backend(attn_backend)
        if prefill_buckets is None:
            prefill_buckets = prefill_bucketing_supported(cfg)
        self.prefill_buckets = prefill_buckets and prefill_bucketing_supported(cfg)

        # -- paged KV pool (kv_block_size set) vs contiguous per-slot slab --
        self.paged = kv_block_size is not None
        self.kv_block_size = kv_block_size
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None and not self.paged:
            raise ValueError("prefill_chunk requires the paged KV cache "
                             "(set kv_block_size)")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.pool = None
        if self.paged:
            if not paged_kv_supported(cfg):
                raise ValueError(
                    "kv_block_size requires a pure global-attention decoder "
                    "(rolling-window / recurrent / encoder state has no pages)"
                )
            if kv_block_size < 1 or kv_block_size % 8:
                # the flash kernel tiles KV in multiples of 8 rows; a block
                # must never straddle a KV tile
                raise ValueError(f"kv_block_size must be a positive multiple "
                                 f"of 8, got {kv_block_size}")
            if max_len % kv_block_size:
                raise ValueError(f"max_len={max_len} not a multiple of "
                                 f"kv_block_size={kv_block_size}")
            nblk = max_len // kv_block_size
            num_blocks = (kv_blocks if kv_blocks is not None
                          else slots * nblk + 1)  # +1: the scratch block
            if mesh is not None:
                # round the pool dim up so it shards evenly on "data"
                dsize = dict(zip(mesh.axis_names, mesh.devices.shape)
                             ).get("data", 1)
                num_blocks += (-num_blocks) % max(dsize, 1)
            self.pool = KVBlockPool(num_blocks, kv_block_size,
                                    prefix_cache=prefix_cache)
            # table row entry 0 = the scratch block (unallocated / retired)
            self.block_tables = np.zeros((slots, nblk), np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
            self.cache = M.init_paged_cache(params, cfg, num_blocks,
                                            kv_block_size)
        else:
            self.cache = M.init_cache(params, cfg, slots, max_len)
        self._slots_sharded = False
        if mesh is not None:
            from jax.sharding import PartitionSpec

            if self.paged:
                from ..dist.sharding import paged_cache_pspecs

                cspecs = paged_cache_pspecs(self.cache, mesh,
                                            self.pool.num_blocks)
            else:
                cspecs = cache_pspecs(self.cache, mesh, slots)
            # report what the pspec rules actually decided (the CLI banner
            # echoes this) instead of re-deriving their divisibility rule
            self._slots_sharded = any(
                "data" in tuple(s) for s in jax.tree.leaves(
                    cspecs, is_leaf=lambda x: isinstance(x, PartitionSpec)
                )
            )
            self.cache = jax.device_put(
                self.cache, to_shardings(cspecs, mesh)
            )
        self.pos = np.zeros(slots, np.int32)
        self.active: list[Request | None] = [None] * slots
        # explicit free-slot list (sorted; lowest slot first, matching the
        # old linear scan's order) — O(log slots) take/release instead of an
        # O(slots) scan per admission.  A slot is NOT free while it is
        # mid-prefill (chunked prefill holds it across rounds).
        self._free_slots: list[int] = list(range(slots))
        self._prefilling: dict[int, dict] = {}  # slot -> chunked-prefill state
        self.prefill_traces = 0
        self.decode_traces = 0
        self.verify_traces = 0

        cfg_ = cfg
        eng = self

        if self.paged:
            @functools.partial(jax.jit, static_argnames=("attn_backend",))
            def _decode_paged(params, cache, token, pos, tables, attn_backend):
                eng.decode_traces += 1  # python body runs only while tracing
                with runtime.use_attn_backend(attn_backend):
                    return M.decode_step(params, cache, token, pos, cfg_,
                                         block_table=tables)

            self._decode = functools.partial(
                _decode_paged, attn_backend=self.attn_backend)

            @functools.partial(jax.jit, static_argnames=("attn_backend",))
            def _prefill_chunk_fn(params, cache, tokens, table, start,
                                  real_end, last_index, attn_backend):
                eng.prefill_traces += 1
                with runtime.use_attn_backend(attn_backend):
                    return M.prefill_chunk(params, tokens, cache, table,
                                           start, real_end, cfg_, last_index)

            self._prefill_chunk_fn = functools.partial(
                _prefill_chunk_fn, attn_backend=self.attn_backend)

            @functools.partial(jax.jit, static_argnames=("attn_backend",))
            def _verify_fn(params, cache, tokens, pos, tables, attn_backend):
                eng.verify_traces += 1  # python body runs only while tracing
                with runtime.use_attn_backend(attn_backend):
                    return M.verify_step(params, cache, tokens, pos, cfg_,
                                         tables)

            self._verify = functools.partial(
                _verify_fn, attn_backend=self.attn_backend)
        else:
            @functools.partial(jax.jit, static_argnames=("attn_backend",))
            def _decode(params, cache, token, pos, attn_backend):
                eng.decode_traces += 1  # python body runs only while tracing
                with runtime.use_attn_backend(attn_backend):
                    return M.decode_step(params, cache, token, pos, cfg_)

            self._decode = functools.partial(_decode,
                                             attn_backend=self.attn_backend)

            @functools.partial(jax.jit, static_argnames=("attn_backend",))
            def _prefill_one(params, tokens, last_index, attn_backend):
                eng.prefill_traces += 1
                with runtime.use_attn_backend(attn_backend):
                    return M.prefill(params, {"tokens": tokens}, cfg_,
                                     max_len=max_len, last_index=last_index)

            self._prefill_one = functools.partial(
                _prefill_one, attn_backend=self.attn_backend)

        # -- speculative decoding (spec_decode=k) ---------------------------
        self.spec_k = int(spec_decode or 0)
        self.draft = None
        if self.spec_k < 0:
            raise ValueError(f"spec_decode must be >= 0, got {spec_decode}")
        if self.spec_k:
            if not kan_deploy:
                raise ValueError(
                    "spec_decode requires kan_deploy=True: the drafter is "
                    "refit from the deployed target's KAN-FFN weights")
            if not self.paged:
                raise ValueError(
                    "spec_decode requires the paged KV cache (set "
                    "kv_block_size): draft rollback releases pool blocks")
            from .spec import DraftModel, DraftSpec

            dspec = (draft_spec if isinstance(draft_spec, DraftSpec)
                     else DraftSpec.parse(draft_spec))
            self.draft = DraftModel(
                float_params, cfg, dspec, slots, max_len,
                kan_backend=self.kan_backend,
                attn_backend=self.attn_backend, mesh=mesh,
            )
        elif draft_spec is not None:
            raise ValueError("draft_spec without spec_decode=k has no effect")

    # -- slot management ------------------------------------------------

    def _free_slot(self):
        """Lowest free slot id, or None — O(1) via the free-slot list."""
        return self._free_slots[0] if self._free_slots else None

    def _take_slot(self, slot: int) -> None:
        i = bisect.bisect_left(self._free_slots, slot)
        if i == len(self._free_slots) or self._free_slots[i] != slot:
            raise RuntimeError(f"slot {slot} is not free "
                               f"(free list: {self._free_slots})")
        self._free_slots.pop(i)

    def release_slot(self, slot: int) -> None:
        """Retire a slot: deactivate it, return its KV blocks to the pool
        (paged) and put it back on the free list.  The scheduler calls this
        when a request finishes; pairs with ``_begin_prefill``/``_admit``."""
        self.active[slot] = None
        self._prefilling.pop(slot, None)
        if self.draft is not None:
            self.draft.release(slot)
        if self.paged:
            for bid in self._slot_blocks[slot]:
                self.pool.release(bid)
            self._slot_blocks[slot] = []
            # point the row at the scratch block: a retired slot still rides
            # the pooled decode step, and its writes must land nowhere real
            self.block_tables[slot] = 0
        bisect.insort(self._free_slots, slot)

    def _padded_prompt(self, prompt: list) -> list:
        """Right-pad to the power-of-two length bucket (token 0 as filler)."""
        if not self.prefill_buckets:
            return list(prompt)
        lb = runtime.bucket_batch(len(prompt))
        if lb > self.max_len - 1:
            return list(prompt)
        return list(prompt) + [0] * (lb - len(prompt))

    def _admit(self, req: Request):
        """Prefill ``req`` into a free slot and greedily pick its first token.

        The scheduler calls :meth:`_prefill_slot` directly (it owns token
        selection — sampling — and metrics); this wrapper keeps the classic
        greedy admission for direct engine use.
        """
        slot = self._free_slot()
        if slot is None:
            raise RuntimeError(
                f"ServeEngine._admit: no free slot for request {req.rid} "
                f"(all {self.slots} busy); check _free_slot() before admitting"
            )
        logits = self._prefill_slot(slot, req)
        req.output.append(int(np.argmax(logits)))

    def _prefill_slot(self, slot: int, req: Request) -> np.ndarray:
        """B=1 prefill of ``req`` into pool ``slot``; returns the (V,)
        first-token logits.  Fills the prompt's cache and activates the
        slot — everything about admission EXCEPT choosing the first token,
        which the caller does (greedy in ``_admit``, sampling and timing in
        the scheduler).  Runs the WHOLE prefill synchronously; the chunked
        path (``_begin_prefill`` + ``_prefill_step`` per scheduling round)
        is how the scheduler keeps a long prompt from stalling the pool."""
        self._begin_prefill(slot, req)
        logits = self._prefill_step(slot)
        while logits is None:
            logits = self._prefill_step(slot)
        return logits

    def _begin_prefill(self, slot: int, req: Request) -> None:
        """Claim ``slot`` for ``req`` and stage its prefill.

        Paged engines match the prompt against the prefix cache here: the
        longest cached FULL-block chain (capped at ``plen - 1`` tokens so
        at least one real token is always prefilled — the first-token
        logits must be computed from something) is spliced into the block
        table copy-free, and prefill starts after it."""
        self._take_slot(slot)
        state = {"req": req, "next": 0}
        if self.paged:
            reused = self.pool.match_prefix(req.prompt,
                                            max_tokens=len(req.prompt) - 1)
            self._slot_blocks[slot] = list(reused)
            for j, bid in enumerate(reused):
                self.block_tables[slot, j] = bid
            state["next"] = len(reused) * self.kv_block_size
        self._prefilling[slot] = state

    def prefilling_slots(self) -> list:
        """Slots currently mid-prefill (claimed, not yet decoding)."""
        return sorted(self._prefilling)

    def _prefill_step(self, slot: int):
        """Advance ``slot``'s staged prefill by one chunk.

        Returns the (V,) first-token logits when the prompt completes (the
        slot becomes active), else None.  Contiguous engines complete in
        one step (the classic whole-prompt prefill + cache splice); paged
        engines advance ``prefill_chunk`` tokens (everything remaining when
        unset) into pool blocks allocated on demand."""
        st = self._prefilling[slot]
        req = st["req"]
        if not self.paged:
            logits = self._prefill_contiguous(slot, req)
        else:
            logits = self._prefill_paged_chunk(slot, st)
            if logits is None:
                return None
        self.pos[slot] = len(req.prompt)
        self.active[slot] = req
        del self._prefilling[slot]
        if self.draft is not None:
            # the drafter needs the prompt in its OWN cache before it can
            # propose for this slot; one cheap B=1 drafter prefill here
            self.draft.prefill_slot(slot, req)
        return logits

    def _prefill_contiguous(self, slot: int, req: Request) -> np.ndarray:
        plen = len(req.prompt)
        # prefill the request alone (B=1), splice its cache into the pool
        tokens = jnp.asarray([self._padded_prompt(req.prompt)], jnp.int32)
        with runtime.use_backend(self.kan_backend), \
                runtime.use_mesh(self.mesh), \
                profile_scope("serve.prefill"):
            logits, cache1 = self._prefill_one(
                self.params, tokens, jnp.asarray([plen - 1], jnp.int32)
            )
        # mask the padding in the cache splice: KV written past the real
        # prompt (pad tokens) is zeroed so no stale state enters the pool.
        tmask = jnp.arange(self.max_len) < plen

        def splice(pool, one):
            one = one[:, 0]                      # (repeats, T, H, D)
            if (self.prefill_buckets and one.ndim >= 2
                    and one.shape[1] == self.max_len):
                one = jnp.where(
                    tmask.reshape((1, -1) + (1,) * (one.ndim - 2)), one, 0
                )
            return pool.at[:, slot].set(one)

        self.cache = jax.tree.map(splice, self.cache, cache1)
        with profile_scope("serve.readback"):
            return np.asarray(logits[0])

    def _prefill_paged_chunk(self, slot: int, st: dict):
        """One chunk of paged prefill; returns final logits or None."""
        req = st["req"]
        plen = len(req.prompt)
        start = st["next"]
        cap = self.prefill_chunk if self.prefill_chunk is not None else plen
        take = min(plen - start, cap)
        # pad the chunk to a power-of-two bucket (same O(log L) compile
        # policy as contiguous prefill) unless that would run past max_len
        c = take
        if self.prefill_buckets:
            lb = runtime.bucket_batch(take)
            if start + lb <= self.max_len:
                c = lb
        bs = self.kv_block_size
        blocks = self._slot_blocks[slot]
        need = -(-(start + take) // bs)          # ceil: blocks covering chunk
        # the span holds the chunk's host work: block allocation, its
        # arguments and the dispatch
        with profile_scope("serve.prefill_chunk"):
            try:
                while len(blocks) < need:
                    bid = self.pool.alloc()
                    self.block_tables[slot, len(blocks)] = bid
                    blocks.append(bid)
            except Exception:
                self.release_slot(slot)
                raise
            chunk = req.prompt[start:start + take] + [0] * (c - take)
            tokens = jnp.asarray([chunk], jnp.int32)
            table = jnp.asarray(self.block_tables[slot])
            with runtime.use_backend(self.kan_backend), \
                    runtime.use_mesh(self.mesh):
                logits, self.cache = self._prefill_chunk_fn(
                    self.params, self.cache, tokens, table,
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(start + take, jnp.int32),
                    jnp.asarray(plen - 1, jnp.int32),
                )
        st["next"] = start + take
        if st["next"] < plen:
            return None
        # publish the prompt's FULL blocks for future prefix hits (cached
        # prefix blocks re-publish as no-ops); partial tail blocks — which
        # decode will keep writing — are never shared
        self.pool.publish_prefix(req.prompt, blocks[:plen // bs])
        with profile_scope("serve.readback"):
            return np.asarray(logits[0])

    def _ensure_decode_blocks(self, horizon: int = 1) -> None:
        """Allocate the pool blocks covering each active slot's next
        ``horizon`` writes (positions ``pos .. pos+horizon-1``, clamped at
        ``max_len`` — writes past it are dropped on device); runs on host
        each round.  ``horizon=1`` is the classic one-token decode step
        (at most one block per slot per call); the speculative verify pass
        needs ``spec_k + 1``."""
        bs = self.kv_block_size
        for i, r in enumerate(self.active):
            if r is None:
                continue
            blocks = self._slot_blocks[i]
            need = -(-min(int(self.pos[i]) + horizon, self.max_len) // bs)
            while len(blocks) < need:
                bid = self.pool.alloc()
                self.block_tables[i, len(blocks)] = bid
                blocks.append(bid)

    def decode_active(self, tokens) -> jax.Array:
        """One pooled decode step over all slots; returns device logits
        (slots, V) and updates the cache in place.  ``pos`` bookkeeping is
        the caller's (the scheduler advances it after selecting tokens)."""
        with profile_scope("serve.decode_step"):
            args = ()
            if self.paged:
                self._ensure_decode_blocks()
                tables = self.block_tables
                if self._prefilling:
                    # mid-prefill slots ride the pooled step with a stale
                    # pos; point their rows at the scratch block so the
                    # step's KV write can't corrupt the blocks their
                    # prefill is filling
                    tables = tables.copy()
                    for s in self._prefilling:
                        tables[s] = 0
                args = (jnp.asarray(tables),)
            with runtime.use_backend(self.kan_backend), \
                    runtime.use_mesh(self.mesh):
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(self.pos), *args,
                )
        return logits

    def verify_active(self, tokens) -> jax.Array:
        """One batched speculative VERIFY pass over all slots.

        ``tokens``: (slots, S) int32, S = spec_k + 1 — row i is the slot's
        last emitted token followed by its draft tokens, occupying
        positions ``pos[i] .. pos[i]+S-1``.  Returns device logits
        (slots, S, V); row j is bit-identical to what ``decode_active``
        would produce after consuming rows 0..j-1 one at a time (see
        ``models.model.verify_step``).  KV for all S positions is written;
        the caller rolls back rejected positions with
        :meth:`truncate_slot`.  ``pos`` bookkeeping is the caller's, same
        as ``decode_active``."""
        s = int(tokens.shape[1])
        with profile_scope("serve.verify"):
            self._ensure_decode_blocks(horizon=s)
            tables = self.block_tables
            if self._prefilling:
                # mid-prefill slots ride along with a stale pos;
                # scratch-redirect their rows exactly as decode_active does
                tables = tables.copy()
                for sl in self._prefilling:
                    tables[sl] = 0
            with runtime.use_backend(self.kan_backend), \
                    runtime.use_mesh(self.mesh):
                logits, self.cache = self._verify(
                    self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(self.pos), jnp.asarray(tables),
                )
        return logits

    def truncate_slot(self, slot: int, new_len: int) -> None:
        """Roll back a slot's KV to ``new_len`` positions after a verify
        round rejected draft tokens: whole tail blocks return to the pool
        (``kvpool.truncate`` guards cached prefix blocks) and their table
        rows point back at the scratch block.  Rejected rows inside the
        kept partial tail block stay — the next verify re-writes them
        before any query can attend them."""
        blocks = self._slot_blocks[slot]
        self.pool.truncate(blocks, new_len)
        self.block_tables[slot, len(blocks):] = 0

    def kv_stats(self) -> dict | None:
        """Paged-pool observability (None on contiguous engines)."""
        if not self.paged:
            return None
        s = self.pool.stats()
        s["prefill_chunk"] = self.prefill_chunk
        s["slot_blocks"] = [len(b) for b in self._slot_blocks]
        return s

    def teacher_forced_logits(self, prompt: list, tokens: list) -> np.ndarray:
        """Logits of ``prompt`` followed by ``tokens``, fed one decode step
        at a time through this engine's own prefill and decode steps
        (chunking, paging, backends and mesh as configured).

        Returns (1 + len(tokens), V) f32: row 0 is the prefill's last-token
        logits, row i+1 the decode logits after consuming ``tokens[i]``.
        Borrows the lowest free slot and releases it before returning —
        how two engine configurations are compared logit for logit on the
        same token stream.
        """
        slot = self._free_slot()
        if slot is None:
            raise RuntimeError("teacher_forced_logits needs a free slot")
        req = Request(rid=-1, prompt=list(prompt),
                      max_new_tokens=len(tokens) + 1)
        try:
            rows = [np.asarray(self._prefill_slot(slot, req))]
            step = np.zeros(self.slots, np.int32)
            for tok in tokens:
                step[slot] = tok
                rows.append(np.asarray(self.decode_active(step)[slot]))
                self.pos[slot] += 1
        finally:
            self.release_slot(slot)
        return np.stack(rows).astype(np.float32)

    # -- main loop --------------------------------------------------------

    def run(self, requests: list, log: Callable | None = None):
        """Serve a batch synchronously; returns requests in completion order.

        Thin driver over :class:`repro.serve.scheduler.Scheduler`: submit
        everything up front (default ``arrival_s=0`` — all available
        immediately), run the event loop to idle.  FIFO admission into free
        slots + one pooled decode step per round is exactly the
        pre-scheduler loop, so greedy token streams are bit-identical to
        it; per-request deadlines/sampling fields are honored if callers
        set them.  Use the scheduler directly for streaming callbacks,
        backpressure and metrics.
        """
        from .scheduler import Scheduler

        sched = Scheduler(self, log=log)
        for req in requests:
            sched.submit(req)
        return sched.run_until_idle()

    def compile_stats(self) -> dict:
        """Engine-level trace counts + the runtime plan-cache counters."""
        return {
            "prefill_traces": self.prefill_traces,
            "decode_traces": self.decode_traces,
            "verify_traces": self.verify_traces,
            "plan_cache": runtime.cache_stats(),
            "mesh": self.mesh_layout(),
            "attn_backend": self.attn_backend,
            "kv": self.kv_stats(),
            "spec": (None if self.draft is None
                     else {"k": self.spec_k, "draft": self.draft.describe()}),
        }

    def mesh_layout(self) -> dict | None:
        """The serving mesh layout (axes x sizes + device count + whether
        the slot pool actually sharded on "data"), or None."""
        if self.mesh is None:
            return None
        return {
            "axes": list(self.mesh.axis_names),
            "shape": [int(s) for s in self.mesh.devices.shape],
            "devices": int(self.mesh.devices.size),
            "slots_sharded": self._slots_sharded,
        }

    def kan_plan_source(self) -> str | None:
        """Where the KAN-FFN pipeline geometry comes from.

        "tuned" when a ``repro.tune`` tile plan is registered for this
        engine's FFN geometry (e.g. loaded from a ``--tuned-config``
        artifact), "heuristic" for the built-in block-size heuristic, None
        when the engine is not serving a KAN-FFN deployment.
        """
        if self.cfg.ffn_kind != "kan":
            return None
        from ..models.layers import kan_ffn_hidden, kan_ffn_specs

        specs = kan_ffn_specs(self.cfg)
        d = self.cfg.d_model
        ov = runtime.PLAN_CACHE.get_tile_overrides(
            (d, kan_ffn_hidden(self.cfg), d), specs, True
        )
        return "tuned" if ov is not None else "heuristic"
