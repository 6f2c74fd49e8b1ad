"""Continuous-batching serving engines (JAX-native, fixed jit shapes).

Two engines share the queue / completion machinery:

``ServeEngine`` — fixed-slot ring-buffer KV.  ``n_slots`` sequences share
one batched DecodeState sized ``(n_slots, max_len)``; prefill runs per
request at a *bucketed* length (prompts are right-padded to the next
multiple of ``prefill_bucket`` and masked via ``n_valid``, so the compile
cache holds at most ``max_len / prefill_bucket`` prefill programs instead
of one per distinct prompt length) and the resulting batch-1 cache is
scattered into the slot's rows.  Every tick is one batched decode step;
idle slots compute garbage — the fixed-shape tax.

``PagedServeEngine`` — vLLM-style paged KV (``serve.kv_pages``).  All
slots share one physical page pool per layer; a host-side ``PagePool``
hands out fixed-size pages at admission (the worst case
``pages_for(prompt + max_new_tokens)`` is reserved up front, so decode
never deadlocks mid-sequence) and a per-slot page table maps logical to
physical pages.  Prefill is *chunked* through the same jitted
``paged_step`` the decode tick uses — one ``prefill_chunk`` tile per
prefilling slot per tick, interleaved with decode — so exactly two
program shapes exist: ``(n_slots, prefill_chunk)`` and ``(n_slots, 1)``.
Pages can store fp, INT8 or FP8 codes with per-(page, head) scales;
``kv="auto"`` follows the policy's ``kv_cache`` mode.

Both engines are token-identical to a straight prefill-then-decode of the
same request (masked rows are zeroed *before* any seq-axis requant, so
bucketing/paging never perturbs quantizer group maxima — see
``nn.attention``).

Quantized serving: pass a policy; weights/activations get ABFP QDQ inside
prefill/decode exactly as in training (the paper's inference story).

Compressed serving (``compress=True``): weights are compressed ONCE at
engine construction against each kernel's *resolved* site rule
(``models.serving_transforms.compress_weights``) and the runtime policy
drops its weight quantizers; qmatmul's ``compressed`` execution backend
then contracts the stored codes directly, so decode never dequantizes a
kernel.  ``engine.weight_bytes`` records the resident-byte accounting.

Expert-resident MoE serving: when ``compress=True`` meets an MoE model
and an ``expert_cache`` capacity is given, the compressed expert banks
are collected into a ``serve.experts.ExpertStore`` — an LRU of
decompressed-dense expert copies fed by a routing-frequency probe at
admission.  ``refresh_experts()`` swaps cache-resident experts into the
params (skipping their per-step dequant); cache state is pure
representation, so hits/misses/refreshes never change tokens.
``expert_stats()`` reports hit/miss + residency split hot/cold.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import messages as msg
from repro.core.policy import (Policy, QuantPolicy, attn_backend_mode,
                               kv_cache_mode)
from repro.core.simulate import compressed_site_tally
from repro.models.lm import DecodeState
from repro.serve import steps as serve_steps
from repro.serve import tracing
from repro.serve.kv_pages import (PageGeometry, PagePool,
                                  attention_read_bytes, check_geometry,
                                  pages_for, resident_kv_bytes)


PROBE_BUCKET = 64  # the expert store's routing probe pads prompts to it


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    eos_id: int | None = None
    # sampling: 0 temperature is exact argmax (bit-identical to the old
    # greedy-only path); top_k <= 0 keeps the full distribution; seed
    # None derives the request's PRNG stream from its uid
    temperature: float = 0.0
    top_k: int = 0
    seed: int | None = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: list  # generated ids (first token from prefill logits included)
    prompt_len: int
    finished_reason: str  # 'eos' | 'length'
    # per-request serving metadata (speculative engines fill these in;
    # plain engines leave the defaults)
    target_steps: int = 0  # verify/decode passes of the target model
    drafted_tokens: int = 0  # draft proposals scored
    accepted_draft_tokens: int = 0  # proposals that survived verify


def _request_key(req: Request) -> jnp.ndarray:
    """Raw (2,) uint32 PRNG key for a request's sampling stream."""
    return jax.random.PRNGKey(req.uid if req.seed is None else req.seed)


class TickBudgetExhausted(RuntimeError):
    """``run_until_done`` ran out of ticks with work still in flight.

    Silently returning the partial ``done`` list (the old behavior) made a
    too-small budget look like a short workload; now the partial results
    travel on the exception instead: ``completions`` holds what finished,
    ``unfinished`` the uids still queued or resident in a slot.
    """

    def __init__(self, max_ticks: int, completions: list, unfinished: list):
        self.max_ticks = max_ticks
        self.completions = completions
        self.unfinished = unfinished
        super().__init__(
            f"tick budget of {max_ticks} exhausted with "
            f"{len(unfinished)} request(s) unfinished (uids {unfinished}); "
            "finished completions are on .completions"
        )


class _EngineBase:
    """Queue / completion bookkeeping shared by both engines."""

    model: object
    params: object
    policy: Policy
    n_slots: int
    max_len: int
    expert_store = None  # set by MoE compressed construction

    def _init_common(self, n_slots: int):
        self.req: list[Request | None] = [None] * n_slots
        self.generated: list[list[int]] = [[] for _ in range(n_slots)]
        self.queue: list[Request] = []
        self.done: list[Completion] = []
        self.ticks = 0
        self._expert_probe_cache = {}  # jitted expert_loads per bucket

    # ------------------------------------------------------- expert store
    def _build_expert_store(self, served, expert_cache: int | None,
                            compress: bool) -> None:
        """Validate the ``expert_cache`` request and, when compressed
        serving meets an MoE model, collect the expert banks into an
        ``ExpertStore`` (per-expert backing entries + LRU caches)."""
        if expert_cache is not None:
            from repro.analysis.messages import (
                expert_cache_requires_compress_message,
                expert_non_moe_message)

            if not compress:
                raise ValueError(expert_cache_requires_compress_message())
            if not getattr(self.model, "is_moe", False):
                raise ValueError(expert_non_moe_message(
                    "an expert cache",
                    getattr(self.model.cfg, "name", "?")))
        if expert_cache is not None and getattr(self.model, "is_moe",
                                                False):
            from repro.serve.experts import ExpertStore

            try:
                self.expert_store = ExpertStore(
                    served, capacity=int(expert_cache or 0),
                    model_name=getattr(self.model.cfg, "name", ""))
            except ValueError:
                # float-rule banks stayed plain dense stacks — nothing
                # to store; serving is dense-resident and trivially
                # token-identical
                self.expert_store = None

    def _observe_experts(self, prompt) -> None:
        """Probe routing loads for an admitted prompt and feed the store.

        The probe pads the prompt to a multiple of ``PROBE_BUCKET``
        tokens, one compile per bucket — pad-token routes only perturb
        the frequency counters, and counters/cache state never enter the
        compute path, so tokens are unaffected."""
        if self.expert_store is None:
            return
        p = np.asarray(prompt, np.int32).reshape(-1)
        padded = -(-len(p) // PROBE_BUCKET) * PROBE_BUCKET
        p = np.concatenate([p, np.zeros(padded - len(p), np.int32)])
        fn = self._expert_probe_cache.get(padded)
        if fn is None:
            fn = jax.jit(lambda params, tokens: self.model.expert_loads(
                params, tokens, policy=self.policy))
            self._expert_probe_cache[padded] = fn
        loads = np.asarray(jax.device_get(
            fn(self.params, jnp.asarray(p[None]))))
        self.expert_store.observe(loads)

    def refresh_experts(self) -> None:
        """Swap cache-resident experts into the serving params (and
        evicted ones back to their compressed entries).  One recompile on
        the next step; tokens are unchanged by construction — the cached
        dense copies equal the dequantized backing entries bit-for-bit."""
        if self.expert_store is None:
            raise ValueError(
                "refresh_experts: engine has no expert store (construct "
                "with compress=True on an MoE model)")
        self.params = self.expert_store.materialize(self.params)

    def expert_stats(self) -> dict | None:
        """The store's residency/traffic report, or None when expert-
        resident serving is inactive."""
        return (None if self.expert_store is None
                else self.expert_store.stats())

    def submit(self, req: Request):
        need = len(req.prompt) + req.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request exceeds engine max_len: prompt of "
                f"{len(req.prompt)} tokens + max_new_tokens="
                f"{req.max_new_tokens} needs {need} > max_len={self.max_len}"
            )
        self.queue.append(req)

    def _completion_extra(self, slot: int) -> dict:
        """Per-request metadata hook (speculative engines override)."""
        return {}

    def _complete(self, slot: int, reason: str):
        req = self.req[slot]
        self.done.append(
            Completion(
                uid=req.uid,
                tokens=list(self.generated[slot]),
                prompt_len=len(req.prompt),
                finished_reason=reason,
                **self._completion_extra(slot),
            )
        )
        self.req[slot] = None
        self.generated[slot] = []

    def _has_work(self) -> bool:
        raise NotImplementedError

    def _resident_uids(self) -> list[int]:
        return [r.uid for r in self.req if r is not None]

    def tick(self):
        raise NotImplementedError

    def run_until_done(self, max_ticks: int = 10_000) -> list[Completion]:
        """Drive ticks until the queue and slots drain.

        Raises ``TickBudgetExhausted`` (with the partial completions
        attached) if work remains after ``max_ticks`` ticks — a truncated
        run must never be mistaken for a finished one.
        """
        spent = 0
        while self._has_work():
            if spent >= max_ticks:
                raise TickBudgetExhausted(
                    max_ticks, list(self.done),
                    self._resident_uids() + [r.uid for r in self.queue])
            self.tick()
            spent += 1
        return self.done


class ServeEngine(_EngineBase):
    """Slot-based continuous batching over a TransformerLM-family model."""

    BATCH_AXIS = 1  # stacked-layer caches: (L, B, ...)

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 512,
        policy: Policy = QuantPolicy(),
        prefill_bucket: int = 64,
        compress: bool = False,
        expert_cache: int | None = None,
    ):
        self.model = model
        mode = kv_cache_mode(policy)  # engine-global cache storage: fail
        # fast on maps whose rules disagree on kv_cache
        if mode == "fp8":
            raise ValueError(msg.fp8_fixed_slot_message())
        self.attn_backend = attn_backend_mode(policy)
        if self.attn_backend == "compressed" and mode != "int8":
            # the decode path would raise this at trace time anyway (QL601);
            # failing here keeps it out of the jit cache
            raise ValueError(msg.compressed_attn_storage_message(
                mode, "the ring-buffer cache"))
        self.weight_bytes = None
        if compress:
            from repro.models import serving_transforms as st

            served = st.compress_weights(params, policy)
            self.weight_bytes = st.weight_bytes_report(params, served)
            self._build_expert_store(served, expert_cache, compress)
            params = served
            policy = st.serving_policy(policy)
        else:
            self._build_expert_store(None, expert_cache, compress)
        self.params = params
        self.policy = policy
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_bucket = prefill_bucket

        state = model.init_decode_state(n_slots, max_len,
                                        kv_quant=(mode == "int8"))
        if not isinstance(state, DecodeState):
            raise TypeError(
                "ServeEngine drives TransformerLM-family models; got "
                f"{type(state).__name__} from "
                f"{type(model).__name__}.init_decode_state"
            )
        self._is_ssm = state.ssm is not None
        self.state = state._replace(
            position=jnp.zeros((n_slots,), jnp.int32)
        )
        self.cur_token = jnp.zeros((n_slots, 1), jnp.int32)
        # host bookkeeping
        self.active = np.zeros(n_slots, dtype=bool)
        # per-slot sampling params + raw PRNG keys (threaded through the
        # jitted decode, which returns the split-off carry keys)
        self._temps = np.zeros(n_slots, np.float32)
        self._topk = np.zeros(n_slots, np.int32)
        self._keys = jnp.zeros((n_slots, 2), jnp.uint32)
        self._init_common(n_slots)

        self._decode = jax.jit(self._decode_fn)
        self._prefill_cache = {}  # jitted prefill per padded length

    # ---------------------------------------------------------- jitted fns
    def _decode_fn(self, params, token, state, keys, temps, topk):
        logits, new_state = self.model.decode_step(
            params, token, state, self.policy
        )
        toks, new_keys = serve_steps.sample_step(logits, keys, temps, topk)
        return toks[:, 0], new_state, new_keys

    def _bucketed(self, S: int) -> int:
        """Pad length for a prompt of S tokens: next bucket multiple,
        capped at max_len.  SSM models prefill at exact length (the
        recurrence would integrate a padded tail — see lm.prefill)."""
        if self._is_ssm:
            return S
        b = self.prefill_bucket
        return min(-(-S // b) * b, self.max_len)

    def _prefill_for(self, padded: int):
        if padded not in self._prefill_cache:
            if self._is_ssm:
                def fn(params, tokens, n_valid):
                    del n_valid  # exact-length prefill
                    return self.model.prefill(
                        params, {"tokens": tokens}, self.policy,
                        max_len=self.max_len,
                    )
            else:
                def fn(params, tokens, n_valid):
                    return self.model.prefill(
                        params, {"tokens": tokens}, self.policy,
                        max_len=self.max_len, n_valid=n_valid,
                    )

            self._prefill_cache[padded] = jax.jit(fn)
        return self._prefill_cache[padded]

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill program shapes built so far (the bucketing
        regression tests assert this stays <= the bucket count)."""
        return len(self._prefill_cache)

    # -------------------------------------------------------------- public
    def _insert_state(self, slot: int, sub: DecodeState, prompt_len: int,
                      first_token: int):
        """Scatter a batch-1 prefill DecodeState into slot ``slot``."""
        b_ax = self.BATCH_AXIS

        def upd(full, part):
            if getattr(full, "ndim", 0) <= b_ax:
                return full  # per-layer scalars (cache length metadata)
            if part.shape[b_ax] != 1:
                raise ValueError(
                    f"prefill state must be batch-1 along axis {b_ax} to "
                    f"scatter into a slot; got shape {part.shape}")
            if (part.shape[:b_ax] != full.shape[:b_ax]
                    or part.shape[b_ax + 1:] != full.shape[b_ax + 1:]):
                raise ValueError(
                    "prefill cache shape mismatch — prefill with the "
                    f"engine's max_len: got {part.shape} vs engine "
                    f"{full.shape} (batch axis {b_ax})")
            start = [0] * full.ndim
            start[b_ax] = slot
            return jax.lax.dynamic_update_slice(
                full, part.astype(full.dtype), tuple(start)
            )

        kv = ssm = None
        if self.state.kv is not None:
            kv = jax.tree_util.tree_map(upd, self.state.kv, sub.kv)
        if self.state.ssm is not None:
            ssm = jax.tree_util.tree_map(upd, self.state.ssm, sub.ssm)
        position = self.state.position.at[slot].set(prompt_len)
        self.state = DecodeState(kv=kv, ssm=ssm, position=position)
        self.cur_token = self.cur_token.at[slot, 0].set(first_token)

    def _admit(self):
        for slot in range(self.n_slots):
            if self.active[slot] or not self.queue:
                continue
            req = self.queue.pop(0)
            self._observe_experts(req.prompt)
            S = len(req.prompt)
            padded = self._bucketed(S)
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :S] = req.prompt
            logits, sub = self._prefill_for(padded)(
                self.params, jnp.asarray(tokens),
                jnp.asarray([S], jnp.int32),
            )
            carry, use = jax.random.split(_request_key(req))
            first_tok = serve_steps.sample_tokens(
                logits[0:1], use[None],
                jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32))
            first = int(jax.device_get(first_tok)[0, 0])
            self._keys = self._keys.at[slot].set(carry)
            self._temps[slot] = req.temperature
            self._topk[slot] = req.top_k
            self.active[slot] = True
            self.req[slot] = req
            self.generated[slot] = [first]
            self._insert_state(slot, sub, S, first)
            if req.eos_id is not None and first == req.eos_id:
                self._evict(slot, "eos")
            elif req.max_new_tokens <= 1:
                self._evict(slot, "length")

    def _evict(self, slot: int, reason: str):
        self._complete(slot, reason)
        self.active[slot] = False

    def _has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any())

    def tick(self):
        """One engine iteration: admit -> batched decode -> evict."""
        self._admit()
        if not self.active.any():
            return
        next_tok, self.state, self._keys = self._decode(
            self.params, self.cur_token, self.state, self._keys,
            jnp.asarray(self._temps), jnp.asarray(self._topk),
        )
        self.cur_token = next_tok.reshape(self.n_slots, 1)
        toks = np.asarray(jax.device_get(next_tok)).reshape(-1)
        self.ticks += 1
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            req = self.req[slot]
            tok = int(toks[slot])
            self.generated[slot].append(tok)
            if req.eos_id is not None and tok == req.eos_id:
                self._evict(slot, "eos")
            elif len(self.generated[slot]) >= req.max_new_tokens:
                self._evict(slot, "length")

    @property
    def utilization(self) -> float:
        return float(self.active.mean())


class PagedServeEngine(_EngineBase):
    """Paged-KV continuous batching: block pool + chunked prefill.

    Admission reserves a request's worst-case page count from the shared
    ``PagePool`` (FCFS — the queue head blocks, which keeps admission
    order deterministic and can never deadlock a running sequence).
    Prefill streams each prompt through the jitted ``paged_step`` one
    ``prefill_chunk`` tile per tick while other slots keep decoding: rows
    not participating in a call carry ``n_valid = 0`` and an all -1 page
    table, so their writes land in the trash page and their position
    doesn't advance — row independence makes the interleaving order
    unobservable in the tokens.
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 512,
        policy: Policy = QuantPolicy(),
        page_size: int = 16,
        n_pages: int | None = None,
        prefill_chunk: int | None = None,
        kv: str = "auto",
        compress: bool = False,
        expert_cache: int | None = None,
    ):
        self.model = model
        mode = kv_cache_mode(policy)
        if kv == "auto":
            kv = {"int8": "int8", "fp8": "fp8"}.get(mode, "fp")
        if kv not in ("fp", "int8", "fp8"):
            raise ValueError(
                f"kv must be 'auto', 'fp', 'int8' or 'fp8'; got {kv!r}")
        if prefill_chunk is None:
            prefill_chunk = max(page_size, -(-64 // page_size) * page_size)
        geo = PageGeometry(page_size=page_size,
                           n_pages=(n_pages if n_pages is not None
                                    else n_slots
                                    * pages_for(max_len, page_size)),
                           max_len=max_len, prefill_chunk=prefill_chunk)
        check_geometry(geo)
        self.geometry = geo
        self.kv = kv
        self.attn_backend = attn_backend_mode(policy)
        if self.attn_backend == "compressed" and kv == "fp":
            # fail at construction, not at trace time inside paged_step
            raise ValueError(msg.compressed_attn_storage_message(
                "fp", "the paged KV pool"))

        self.weight_bytes = None
        if compress:
            from repro.models import serving_transforms as st

            served = st.compress_weights(params, policy)
            self.weight_bytes = st.weight_bytes_report(params, served)
            self._build_expert_store(served, expert_cache, compress)
            params = served
            policy = st.serving_policy(policy)
        else:
            self._build_expert_store(None, expert_cache, compress)
        self.params = params
        self.policy = policy
        self.n_slots = n_slots
        self.max_len = max_len

        # raises TypeError for SSM families — pages only make sense for
        # attention's O(T) cache
        self.state = model.init_paged_state(
            n_slots, page_size=geo.page_size, n_pages=geo.n_pages,
            max_pages_per_seq=geo.max_pages_per_seq, kv=kv)
        self.pool = PagePool(geo.n_pages)
        self.table = np.full((n_slots, geo.max_pages_per_seq), -1, np.int32)
        self.slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
        self.active = np.zeros(n_slots, dtype=bool)      # decoding
        self.prefilling = np.zeros(n_slots, dtype=bool)  # mid-prefill
        self._pf_pos = [0] * n_slots  # prompt tokens consumed so far
        self._cur = np.zeros((n_slots, 1), np.int32)
        self._temps = np.zeros(n_slots, np.float32)
        self._topk = np.zeros(n_slots, np.int32)
        self._keys = jnp.zeros((n_slots, 2), jnp.uint32)
        self._init_common(n_slots)

        # the state is donated: the step updates the page pool in place
        self._step = jax.jit(self._step_fn, donate_argnums=(2,))
        # compressed matmul sites of each step program, by contraction
        self._sites = {"prefill": {}, "decode": {}}
        # pages a decode row reads at a time where decode reads each row's
        # live pages in place; None where it gathers the whole table
        self._read_unit = model.paged_read_unit(policy, kv, geo.page_size)

    # ---------------------------------------------------------- jitted fns
    def _step_fn(self, params, tokens, state, n_valid, keys, temps, topk):
        with compressed_site_tally() as sites:
            logits, state = self.model.paged_step(
                params, tokens, state, n_valid=n_valid, policy=self.policy)
        # runs while the step traces: one tally per step program
        self._sites["decode" if tokens.shape[1] == 1 else "prefill"] = sites
        with jax.named_scope("sample"):
            toks, new_keys = serve_steps.sample_step(logits, keys, temps,
                                                     topk)
        return toks[:, 0], state, new_keys

    def compile_steps(self) -> dict:
        """Compile both step shapes ahead of the first tick — the prefill
        chunk ``(n_slots, prefill_chunk)`` and decode ``(n_slots, 1)`` —
        and return the compiled programs by name."""
        n = self.n_slots
        return {
            name: self._step.lower(
                self.params, jnp.zeros((n, width), jnp.int32), self.state,
                jnp.zeros((n,), jnp.int32), self._keys,
                jnp.asarray(self._temps), jnp.asarray(self._topk),
            ).compile()
            for name, width in (("prefill", self.geometry.prefill_chunk),
                                ("decode", 1))
        }

    def _masked_table(self, mask: np.ndarray) -> jnp.ndarray:
        """Device table with non-participating rows unmapped (-1): their
        writes route to the trash page inside the step."""
        return jnp.asarray(
            np.where(mask[:, None], self.table, -1).astype(np.int32))

    # ------------------------------------------------------------ admission
    def _admit(self):
        while self.queue:
            free = [s for s in range(self.n_slots)
                    if not self.active[s] and not self.prefilling[s]]
            if not free:
                return
            req = self.queue[0]
            need = pages_for(len(req.prompt) + req.max_new_tokens,
                             self.geometry.page_size)
            pages = self.pool.alloc(need)
            if pages is None:
                return  # FCFS: the head waits for pages; no overtaking
            self.queue.pop(0)
            self._observe_experts(req.prompt)
            slot = free[0]
            self.slot_pages[slot] = pages
            self.table[slot, :] = -1
            self.table[slot, :need] = pages
            self.prefilling[slot] = True
            self.req[slot] = req
            self.generated[slot] = []
            self._pf_pos[slot] = 0
            self._temps[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._keys = self._keys.at[slot].set(_request_key(req))
            self.state = self.state._replace(
                position=self.state.position.at[slot].set(0))

    # -------------------------------------------------------------- prefill
    def _prefill_tick(self):
        rows = [s for s in range(self.n_slots) if self.prefilling[s]]
        if not rows:
            return
        with tracing.span("serve.prefill") as counts:
            with tracing.span("serve.prepare"):
                C = self.geometry.prefill_chunk
                tokens = np.zeros((self.n_slots, C), np.int32)
                n_valid = np.zeros((self.n_slots,), np.int32)
                for s in rows:
                    p = self.req[s].prompt
                    off = self._pf_pos[s]
                    m = min(C, len(p) - off)
                    tokens[s, :m] = p[off:off + m]
                    n_valid[s] = m
                state = self.state._replace(pages=self.state.pages._replace(
                    table=self._masked_table(self.prefilling)))
                args = (self.params, jnp.asarray(tokens), state,
                        jnp.asarray(n_valid), self._keys,
                        jnp.asarray(self._temps), jnp.asarray(self._topk))
            counts.update(self._step_counts(
                n_valid, tokens.size,
                [self._pf_pos[s] + int(n_valid[s]) for s in rows],
                self.table.size))
            with tracing.span("serve.step"):
                tok, state, self._keys = self._step(*args)
            counts.update(self._sites["prefill"])
            with tracing.span("serve.readback"):
                # the step's routing counts come back with its tokens
                toks, routed = jax.device_get((tok, state.stats))
                toks = np.asarray(toks).reshape(-1)
            counts.update({k: int(v) for k, v in routed.items()})
            with tracing.span("serve.update"):
                self.state = state
                for s in rows:
                    self._pf_pos[s] += int(n_valid[s])
                    if self._pf_pos[s] < len(self.req[s].prompt):
                        continue
                    first = int(toks[s])
                    self.prefilling[s] = False
                    self.active[s] = True
                    self.generated[s] = [first]
                    self._cur[s, 0] = first
                    req = self.req[s]
                    if req.eos_id is not None and first == req.eos_id:
                        self._evict(s, "eos")
                    elif req.max_new_tokens <= 1:
                        self._evict(s, "length")

    # --------------------------------------------------------------- decode
    def _decode_tick(self):
        rows = [s for s in range(self.n_slots) if self.active[s]]
        if not rows:
            return
        with tracing.span("serve.decode") as counts:
            with tracing.span("serve.prepare"):
                n_valid = self.active.astype(np.int32)
                state = self.state._replace(pages=self.state.pages._replace(
                    table=self._masked_table(self.active)))
                args = (self.params, jnp.asarray(self._cur), state,
                        jnp.asarray(n_valid), self._keys,
                        jnp.asarray(self._temps), jnp.asarray(self._topk))
            # each row writes its last token after the context it holds
            context = [len(self.req[s].prompt) + len(self.generated[s])
                       for s in rows]
            counts.update(self._step_counts(
                n_valid, self._cur.size, context,
                self._decode_pages_read(context)))
            with tracing.span("serve.step"):
                tok, state, self._keys = self._step(*args)
            counts.update(self._sites["decode"])
            with tracing.span("serve.readback"):
                toks, routed = jax.device_get((tok, state.stats))
                toks = np.asarray(toks).reshape(-1)
            counts.update({k: int(v) for k, v in routed.items()})
            with tracing.span("serve.update"):
                self.state = state
                for slot in rows:
                    req = self.req[slot]
                    t = int(toks[slot])
                    self.generated[slot].append(t)
                    self._cur[slot, 0] = t
                    if req.eos_id is not None and t == req.eos_id:
                        self._evict(slot, "eos")
                    elif len(self.generated[slot]) >= req.max_new_tokens:
                        self._evict(slot, "length")

    def _decode_pages_read(self, context: list[int]) -> int:
        """Pages a decode call reads: each participating row's live pages
        in whole read units where decode reads them in place, else every
        page-table entry (the gather)."""
        u = self._read_unit
        if u is None:
            return self.table.size
        ps = self.geometry.page_size
        return sum(-(-pages_for(n, ps) // u) * u for n in context)

    def _step_counts(self, n_valid: np.ndarray, computed: int,
                     context: list[int], pages_read: int) -> dict:
        """Waste counts of one step call, from host state alone.

        ``rows_valid`` / ``rows_computed``: token rows that carry a prompt
        or decode token, against the rows the step computes;
        ``pages_live`` / ``pages_read``: pages that hold the participating
        rows' ``context`` after the call, against the pages the step reads
        (a prefill call gathers every page-table entry).
        """
        ps = self.geometry.page_size
        return {"rows_valid": int(n_valid.sum()), "rows_computed": computed,
                "pages_live": sum(pages_for(n, ps) for n in context),
                "pages_read": pages_read}

    def _evict(self, slot: int, reason: str):
        self._complete(slot, reason)
        self.pool.free(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.table[slot, :] = -1
        self.active[slot] = False
        self.prefilling[slot] = False

    # -------------------------------------------------------------- driver
    def _has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any()) \
            or bool(self.prefilling.any())

    def tick(self):
        """Admit -> one prefill chunk per prefilling slot -> one decode
        step over the active slots."""
        with tracing.span("serve.tick"):
            with tracing.span("serve.admit") as counts:
                queued = len(self.queue)
                self._admit()
                counts["admitted"] = queued - len(self.queue)
                # a free slot left over means the head waits for pages
                counts["blocked"] = int(bool(self.queue) and not (
                    self.active | self.prefilling).all())
            self._prefill_tick()
            self._decode_tick()
        self.ticks += 1

    # ----------------------------------------------------------- reporting
    @property
    def utilization(self) -> float:
        return float((self.active | self.prefilling).mean())

    def page_stats(self) -> dict:
        return self.pool.stats()

    def kv_bytes(self) -> dict:
        """Resident KV bytes at the CURRENT pool occupancy (see
        ``kv_pages.resident_kv_bytes`` for the equivalents), plus the
        attention-path *read* accounting: the bytes one decode step pulls
        from the KV store, which depends on the attention backend — the
        compressed backend reads codes + page scales only, while the
        QDQ-sim paths also materialize a dense round-trip copy."""
        c = self.model.cfg
        out = resident_kv_bytes(
            self.pool.in_use, page_size=self.geometry.page_size,
            n_kv=c.n_kv, head_dim=c.head_dim_, n_layers=c.n_layers,
            kv=self.kv, fp_bytes=jnp.dtype(c.dtype).itemsize)
        out.update(attention_read_bytes(
            self.pool.in_use * self.geometry.page_size,
            n_kv=c.n_kv, head_dim=c.head_dim_, n_layers=c.n_layers,
            kv=self.kv, backend=self.attn_backend,
            fp_bytes=jnp.dtype(c.dtype).itemsize,
            page_size=self.geometry.page_size))
        return out
