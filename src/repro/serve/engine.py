"""Serving engines: prefix-cached paged continuous batching (PagedEngine)
and the legacy dense-slot baseline (Engine).

:class:`PagedEngine` is the production path: a ref-counted, shared-page
KV pool (serve/kv_cache.py) with token-level continuous batching,
chunked prefill, prefix caching and recompute-preemption
(serve/scheduler.py). On admission each prompt is hashed block-by-block
against the page index; matched pages are attached (refcount++), the
sequence starts ``prefilled`` at the cached boundary, and only the tail
is prefilled through the existing ``q_start`` path. Pages are allocated
on demand per step; a write into a shared page is copy-on-write (the
cache hands back (src, dst) page copies which the engine replays on
device before the model step). Decode attention and prefill-chunk
attention both stream pages through ``flash_e2softmax_pallas``'s paged
variants, so SOLE's quantized online-softmax correction runs in the
serving hot loop exactly as the paper's streaming unit intends.

:class:`Engine` keeps the old dense ``batch x max_len`` slot cache and
the unfused XLA decode path — the memory/throughput baseline that
benchmarks/serve_throughput.py and the paged-vs-dense equivalence tests
compare against.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.configs.base import ArchConfig
from repro.models import api
from repro.serve.kv_cache import PagedKVCache, copy_pages
from repro.serve.sampling import apply_finish, eos_table, sampler_for
from repro.serve.scheduler import Scheduler, Sequence
from repro.serve.state import StateCheckpointCache, StateSlotPool
from repro.sharding import rules as R

Array = jax.Array
# Host spans on the profiler's clock: recorded only while a profiler is
# collecting, one inactive TraceMe each otherwise.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0     # 0 = greedy argmax
    top_k: int = 0               # 0 = full vocab
    seed: int = 0                # per-request sampling stream
    # finish events (see serve/sampling.py): sampling any of eos_ids
    # ends the request ("eos"); stop holds multi-token sequences
    # matched over the generated tokens ("stop"). The finishing token /
    # sequence is kept in the output; anything after it is discarded.
    eos_ids: Tuple[int, ...] = ()
    stop: Tuple[Tuple[int, ...], ...] = ()
    # encdec only: raw encoder input (S_enc, D) or (1, S_enc, D), run
    # once per admission.
    frames: Optional[np.ndarray] = None
    out: Optional[List[int]] = None


def _run_ctx(rules: Optional[R.Rules]):
    """(mesh context, rules context) for a generate() call."""
    if rules is not None:
        return rules.mesh, R.use_rules(rules)
    import contextlib
    return contextlib.nullcontext(), contextlib.nullcontext()


class PagedEngine:
    """Continuous-batching engine over paged **sequence state**.

    One engine class serves every family in the repo: the family's
    :class:`repro.models.state.SequenceStateSpec` declares which pools
    its per-sequence state lives in — ref-counted KV pages (attention
    layers), fixed-size recurrent state slots (rwkv6/rglru layers;
    serve/state.py), read-only shared cross pages (whisper's encoder
    output) — and which features (prefix cache, speculative decoding,
    COW fork) are legal; unsupported features raise at construction
    rather than silently degrading. All model calls dispatch through
    ``models.api`` — the engine never imports a family module.

    Three jitted steps drive the whole loop (the composite state is
    donated — pools and slots are updated in place):

      * ``_prefill``: one chunk of one sequence's replay (B=1, C static;
        padded tail writes route to the null page via ``n_valid``);
      * ``_decode_h``: a **decode horizon** — H fused decode+sample
        steps for up to ``decode_batch`` sequences in one jitted
        ``lax.scan`` (lane count static; short batches are padded with
        null-page lanes). Sampling runs in-jit on the counter-keyed
        threefry stream (serve/sampling.py), so only the (B, H) sampled
        ids ever reach the host — the per-token (B, padded_vocab)
        logits transfer and per-token dispatch are gone. The scheduler
        bounds H by the next scheduling event (finish / pending
        prefill), the cache pre-extends each lane's page table for all
        H tokens (COW copies applied up front), and H is floored to a
        power of two so at most ``log2(decode_horizon)+1`` scan shapes
        ever compile;
      * ``_copy``: one page duplicated across layers/pools (COW);
      * ``_verify`` (speculative decoding, ``spec_config`` set): one
        batched K+1-wide target forward scoring every lane's drafted
        tokens, with the pinned counter-keyed draws computed in-jit —
        the engine accepts the longest draft prefix matching them, so
        output streams stay bit-for-bit identical to plain decode
        while each verify dispatch can emit up to K+1 tokens per lane
        (see serve/spec.py).

    Attention implementations resolve through the ``repro.ops``
    registry: ``backend="pallas"`` streams pages through the paged flash
    kernels; ``backend="reference"`` gathers pages and reuses the XLA
    softmax path (oracle for equivalence tests, and the fallback for
    softmax modes the kernel does not implement). ``backend=None``
    resolves from ``cfg.ops_backend`` with the standard autodetect
    (``auto`` = compiled kernels on TPU, XLA reference elsewhere).
    """

    def __init__(self, cfg: ArchConfig, params, *, num_blocks: int = 64,
                 block_size: int = 16, max_seq_len: int = 256,
                 max_running: int = 8, decode_batch: int = 4,
                 prefill_chunk: int = 16, decode_horizon: int = 8,
                 backend: Optional[str] = None,
                 prefix_cache: Optional[bool] = None, watermark: int = 1,
                 rules: Optional[R.Rules] = None, param_axes=None,
                 spec_config=None):
        # the family's sequence-state shape drives everything below:
        # which pools exist, which features are legal, how admission
        # accounts footprint.
        state_spec = api.sequence_state_spec(cfg)
        if not state_spec.servable:
            raise ValueError(
                f"family {cfg.family!r} is not paged-servable "
                "(see its sequence_state_spec)")
        if cfg.window and max_seq_len > cfg.window:
            raise ValueError(
                "pages are append-only: serving past the sliding window "
                f"(max_seq_len {max_seq_len} > window {cfg.window}) would "
                "keep dead KV resident; cap max_seq_len at the window")
        # prefix_cache is tri-state: None = what the family supports;
        # an explicit True on an unsupported family is a hard error, not
        # a silent downgrade.
        if prefix_cache is None:
            prefix_cache = state_spec.supports_prefix_cache
        elif prefix_cache and not state_spec.supports_prefix_cache:
            raise ValueError(
                f"family {cfg.family!r} does not support prefix caching "
                "(its sequence state cannot be restored at a matched "
                "boundary)")
        if spec_config is not None and not state_spec.supports_spec_decode:
            raise ValueError(
                f"family {cfg.family!r} does not support speculative "
                "decoding (its sequence state cannot rewind rejected "
                "drafts)")
        if backend is None:
            backend = ops.backend_for(cfg, "paged_attention",
                                      cfg.softmax_mode)
        if decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {decode_horizon}")
        self.cfg = cfg
        self.state_spec = state_spec
        self.prefix_cache = prefix_cache
        # w8a16/w8a8: pack every projection weight to int8 + per-channel
        # fp scales *before* layout (the packed {"q","s"} leaves carry
        # mirrored axes, so the sharding rules below still apply).
        # quantize_params is idempotent — replica engines re-feeding an
        # already-quantized tree pass through untouched.
        if cfg.quant.weights:
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"family {cfg.family!r} has no quantized serving "
                    "path (quant.weights is dense/moe-only)")
            params = R.quantize_params(params)
            if param_axes is not None:
                param_axes = R.quantize_param_axes(param_axes)
        # with a mesh + the logical-axes tree from api.init_params, lay
        # the weights out up front (heads/ff over model, divisibility
        # fallback per dim) instead of letting the first jitted step
        # replicate them everywhere.
        self.params = (R.shard_params(params, param_axes, rules)
                       if rules is not None and param_axes is not None
                       else params)
        self.decode_batch = decode_batch
        self.decode_horizon = decode_horizon
        self.backend = backend
        self.rules = rules
        self.model = api.get_model(cfg)
        # the cache is always constructed — a pure-recurrent family gets
        # zero-byte pools (kv_layers=0) with every host-side invariant
        # (free lists, leak checks, sanitizer budgets) intact.
        self.cache = PagedKVCache(cfg, num_blocks=num_blocks,
                                  block_size=block_size,
                                  max_seq_len=max_seq_len,
                                  prefix_cache=(prefix_cache
                                                and state_spec.has_pages),
                                  kv_layers=state_spec.kv_layers)
        if rules is not None:
            self.cache.shard(rules)
        # recurrent families: one fixed-size state slot per running lane
        # (+ the null slot), and — when prefix caching is on — the
        # block-boundary checkpoint cache that stands in for page
        # sharing (serve/state.py).
        self.slot_pool = None
        self.ckpts = None
        if state_spec.has_slots:
            self.slot_pool = StateSlotPool(state_spec,
                                           num_slots=max_running + 1)
            if rules is not None:
                self.slot_pool.shard(rules)
            if prefix_cache:
                self.ckpts = StateCheckpointCache(block_size=block_size)
        self.sched = Scheduler(self.cache, max_running=max_running,
                               prefill_chunk=prefill_chunk,
                               watermark=watermark, spec=state_spec,
                               slots=self.slot_pool, ckpts=self.ckpts)
        # speculative decoding (serve/spec.py): drafter + K controller.
        # A draft model must share the target's vocab — acceptance
        # compares draft ids against pinned draws over cfg.vocab_size.
        self.spec = spec_config
        if spec_config is not None:
            dv = getattr(spec_config.drafter, "vocab_size", None)
            if dv is not None and dv != cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab {dv} != target vocab "
                    f"{cfg.vocab_size}: speculation needs a shared "
                    "tokenizer")
        self.steps = 0
        self.decode_tokens = 0
        self.decode_dispatches = 0
        self.truncated_tokens = 0        # horizon-tail draws discarded
        self.reclaimed_pages = 0         # pages handed back by truncate
        self.spec_dispatches = 0         # verify dispatches issued
        self.spec_proposed = 0           # draft tokens sent to verify
        self.spec_accepted = 0           # draft tokens accepted
        self.spec_fallbacks = 0          # decode steps spec handed back
        self.finish_reasons: Dict[str, int] = {}
        self._finished: Dict[int, List[int]] = {}

        def _prefill(params, state, tokens, q_start, n_valid, refs):
            return api.prefill_paged(params, tokens, q_start, n_valid,
                                     refs, state, cfg, backend=backend)

        def _decode_h(params, state, token, pos, refs, temperature,
                      top_k, seed, counter, eos_ids, num_steps, use_top_k,
                      stochastic, use_eos):
            return api.decode_horizon_paged(
                params, token, pos, refs, state, temperature, top_k,
                seed, counter, eos_ids, cfg, num_steps=num_steps,
                use_top_k=use_top_k, stochastic=stochastic,
                use_eos=use_eos, backend=backend)

        self._prefill = jax.jit(_prefill, donate_argnums=(1,))
        self._decode_h = jax.jit(_decode_h, donate_argnums=(1,),
                                 static_argnums=(10, 11, 12, 13))
        if state_spec.supports_spec_decode:
            def _verify(params, state, tokens, q_start, n_valid, refs,
                        temperature, top_k, seed, counter, eos_ids,
                        use_top_k, stochastic, use_eos):
                return api.verify_paged(
                    params, tokens, q_start, n_valid, refs, state,
                    temperature, top_k, seed, counter, eos_ids, cfg,
                    use_top_k=use_top_k, stochastic=stochastic,
                    use_eos=use_eos, backend=backend)
            self._verify = jax.jit(_verify, donate_argnums=(1,),
                                   static_argnums=(11, 12, 13))
        if state_spec.has_pages:
            self._copy = jax.jit(copy_pages, donate_argnums=(0,))
        if state_spec.has_slots:
            # slot lifecycle ops: read one sequence's slot (checkpoint
            # snapshot), load a host checkpoint into a fresh slot, and
            # zero-fill a cold slot (a slot's device contents are stale
            # garbage from its previous owner at acquire time).
            self._snap = jax.jit(
                lambda slots, i: jax.tree.map(lambda s: s[i], slots))
            self._load_slot = jax.jit(
                lambda slots, i, val: jax.tree.map(
                    lambda s, v: s.at[i].set(v.astype(s.dtype)),
                    slots, val),
                donate_argnums=(0,))
            self._zero_slot = jax.jit(
                lambda slots, i: jax.tree.map(
                    lambda s: s.at[i].set(jnp.zeros_like(s[i])), slots),
                donate_argnums=(0,))
        if state_spec.cross_tokens:
            def _encode(params, frames, cross_row, state):
                return api.encode_paged(params, frames, cross_row, state,
                                        cfg)
            self._encode = jax.jit(_encode, donate_argnums=(3,))

    def _apply_copies(self, copies: List[Tuple[int, int]]) -> None:
        """Replay COW page duplications on device, before the step that
        writes into the fresh private pages. Pairs are padded to a
        power-of-two count with harmless null->null copies, so the whole
        batch is one dispatch from a handful of compiled shapes."""
        if not copies:
            return
        n = 1
        while n < len(copies):
            n *= 2
        src, dst = zip(*(copies + [(0, 0)] * (n - len(copies))))
        # stage through dtyped np arrays: list/tuple -> device counts as
        # an implicit transfer under the decode-loop transfer guard.
        self.cache.pools = self._copy(self.cache.pools,
                                      jnp.asarray(np.array(src, np.int32)),
                                      jnp.asarray(np.array(dst, np.int32)))

    # -- composite sequence state ---------------------------------------------

    def _state(self) -> Dict[str, object]:
        """The family's device state for one jitted step: page pools
        and/or the slot tree, keyed the way ``models.api`` dispatch
        expects. Built fresh per call — the step donates it and
        :meth:`_put_state` writes the returned arrays back."""
        st: Dict[str, object] = (dict(self.cache.pools)
                                 if self.state_spec.has_pages else {})
        if self.slot_pool is not None:
            st["slots"] = self.slot_pool.slots
        return st

    def _put_state(self, state: Dict[str, object]) -> None:
        if self.state_spec.has_pages:
            self.cache.pools = {"k": state["k"], "v": state["v"]}
        if self.slot_pool is not None:
            self.slot_pool.slots = state["slots"]

    def _refs(self, seqs: List[Optional[Sequence]]) -> Dict[str, Array]:
        """Per-lane state references (page tables / slot ids / cross
        tables) for a padded batch; ``None`` lanes get null routes."""
        sids = [s.seq_id if s is not None else None for s in seqs]
        spec = self.state_spec
        refs: Dict[str, Array] = {}
        if spec.has_pages:
            refs["tables"] = jnp.asarray(self.cache.batch_tables(sids))
        if self.slot_pool is not None:
            refs["slots"] = jnp.asarray(self.slot_pool.batch_slots(sids))
        if spec.cross_tokens:
            cb = self.cache.blocks_for_tokens(spec.cross_tokens)
            refs["cross"] = jnp.asarray(self.cache.batch_cross(sids, cb))
            # null lanes claim one valid cross token: an all-masked
            # softmax row would be NaN, so they attend one garbage
            # null-page key instead (the self-attention null-lane
            # precedent: kv_len = pos + 1 = 1).
            cv = np.array([s.cross_valid if s is not None else 1
                           for s in seqs], np.int32)
            refs["cross_valid"] = jnp.asarray(cv)
        return refs

    def _init_state(self, seq: Sequence) -> None:
        """Once per admission, before the first prefill chunk: make the
        sequence's non-page state real — zero-fill or checkpoint-restore
        its recurrent slot, and (encdec) run the encoder once, parking
        cross K/V in the pages the scheduler reserved."""
        if seq.state_ready:
            return
        if self.slot_pool is not None:
            idx = jnp.asarray(np.int32(self.slot_pool.slot_of(seq.seq_id)))
            if seq._restore is not None:
                val = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                                   seq._restore)
                self.slot_pool.slots = self._load_slot(
                    self.slot_pool.slots, idx, val)
                seq._restore = None
            else:
                self.slot_pool.slots = self._zero_slot(
                    self.slot_pool.slots, idx)
        if self.state_spec.cross_tokens:
            if seq.frames is None:
                raise ValueError(
                    f"family {self.cfg.family!r} needs encoder frames on "
                    "every request (Request.frames)")
            frames = np.asarray(seq.frames, np.float32)
            if frames.ndim == 2:
                frames = frames[None]
            seq.cross_valid = max(
                1, min(frames.shape[1], self.state_spec.cross_tokens))
            cb = self.cache.blocks_for_tokens(self.state_spec.cross_tokens)
            row = jnp.asarray(self.cache.cross_row(seq.seq_id, cb)[None])
            self._put_state(self._encode(self.params, jnp.asarray(frames),
                                         row, self._state()))
        seq.state_ready = True

    def _maybe_checkpoint(self, seq: Sequence, boundary: int) -> None:
        """After a prefill chunk ending at ``boundary`` replay tokens:
        snapshot the slot to host and register it under the prompt's
        chain keys — iff the boundary is block-aligned and strictly
        inside the prompt (the final position is always recomputed, like
        the page cache's ``len(prompt) - 1`` cap)."""
        if self.ckpts is None or seq.prefix_keys is None:
            return
        if (boundary % self.cache.block_size != 0 or boundary <= 0
                or boundary > seq.prompt_len - 1):
            return
        idx = jnp.asarray(np.int32(self.slot_pool.slot_of(seq.seq_id)))
        snap = self._snap(self.slot_pool.slots, idx)
        # whole-array d2h (guard-sanctioned), one leaf at a time
        self.ckpts.register(seq.prefix_keys, boundary,
                            jax.tree.map(np.asarray, snap))

    # -- one engine iteration -------------------------------------------------

    def _prefill_step(self, seq: Sequence) -> None:
        c = self.sched.prefill_chunk
        start = seq.prefilled
        replay = seq.replay_tokens
        real = min(c, len(replay) - start)
        with _span("engine.prefill", seq=seq.seq_id, start=start,
                   rows=real):
            with _span("engine.stage"):
                copies = self.sched.ensure_tokens(seq, start, start + real)
                if copies is None:
                    return               # seq itself was preempted
                self._apply_copies(copies)
                self._init_state(seq)
                chunk = np.zeros((1, c), np.int32)
                chunk[0, :real] = replay[start:start + real]
                refs = self._refs([seq])
            with _span("engine.dispatch"):
                logits, state = self._prefill(
                    self.params, self._state(), jnp.asarray(chunk),
                    jnp.asarray(np.array([start], np.int32)),
                    jnp.asarray(np.array([real], np.int32)),
                    refs)
            self._put_state(state)
            seq.prefilled = start + real
            self._maybe_checkpoint(seq, start + real)
            if seq.in_prefill:
                return
            if self.state_spec.has_pages:
                self.cache.register_prompt(seq.seq_id, seq.prompt,
                                           seq.prefix_keys)
            if not seq.out:
                # fresh sequence: sample the first generated token from
                # the last *real* prompt position's logits. A resumed
                # sequence already holds its next feed token in out.
                # whole-array d2h, then host indexing: indexing the
                # device array first would transfer the index scalars
                # h2d, tripping the decode-loop transfer guard.
                with _span("engine.sync"):
                    row = np.asarray(logits)[0, real - 1]
                with _span("engine.finish"):
                    tok = seq.sampler(row)
                    # the very first token can already be a finish event
                    # (eos, or a single-token stop sequence): the
                    # sequence must never enter a decode batch.
                    _, seq.finish_reason = apply_finish(seq.sampler,
                                                        seq.out, [tok])

    def _decode_step(self) -> None:
        batch = self.sched.decode_batch(self.decode_batch)
        # horizon: largest event-safe token count, floored to a power of
        # two so the scan compiles at most log2(decode_horizon)+1 shapes.
        h = self.sched.decode_horizon(batch, self.decode_horizon)
        if h == 0:
            return
        h = 1 << (h.bit_length() - 1)
        with _span("engine.decode", h=h) as span:
            with _span("engine.stage"):
                lanes: List[Sequence] = []
                for seq in batch:
                    if seq not in self.sched.running:
                        continue         # preempted by an earlier lane
                    pos = seq.prompt_len + len(seq.out) - 1
                    # pre-extend the page table for the whole horizon:
                    # every page the in-jit scan will write exists, and
                    # is private (COW copies surfaced here), before
                    # dispatch.
                    copies = self.sched.ensure_tokens(seq, pos, pos + h)
                    if copies is None:
                        continue
                    self._apply_copies(copies)
                    lanes.append(seq)
                # victim policy invariant: ensure_tokens preempts
                # youngest-first and stops at the requesting seq, so a
                # later lane's growth can only evict lanes *after* it in
                # running order — never one already collected above.
                # Device writes rely on this.
                assert all(s in self.sched.running for s in lanes)
                if not lanes:
                    return
                d = self.decode_batch
                token = np.zeros((d,), np.int32)
                pos = np.zeros((d,), np.int32)
                temp = np.zeros((d,), np.float32)  # null lanes decode greedily
                topk = np.zeros((d,), np.int32)
                seed = np.zeros((d,), np.uint32)
                ctr = np.zeros((d,), np.int32)
                seqs: List[Optional[Sequence]] = [None] * d
                for i, seq in enumerate(lanes):
                    token[i] = seq.out[-1]
                    pos[i] = seq.prompt_len + len(seq.out) - 1
                    s = seq.sampler
                    temp[i], topk[i], seed[i] = s.temperature, s.top_k, s.seed
                    # token n draws with counter n: the host sampler spent
                    # counter 0 on the prefill-logits token, so the device
                    # stream continues exactly where it left off.
                    ctr[i] = len(seq.out)
                    seqs[i] = seq
                refs = self._refs(seqs)
                # static sampling fast paths: skipping the top-k rank
                # sorts / Gumbel rows / eos membership tests is an exact
                # identity for lanes that don't use them, so flags from
                # the live batch never change any draw. The eos table
                # width is pow2-rounded so lane mixes compile a handful
                # of shapes, not one per mix.
                use_top_k = any(s.sampler.top_k > 0 for s in lanes)
                stochastic = any(s.sampler.temperature > 0 for s in lanes)
                widest = max(len(s.sampler.eos_ids) for s in lanes)
                use_eos = widest > 0
                eos = np.full((d, 1), -1, np.int32)
                if use_eos:
                    width = 1 << (widest - 1).bit_length() if widest > 1 else 1
                    eos = np.full((d, width), -1, np.int32)
                    eos[:len(lanes)] = eos_table([s.sampler for s in lanes],
                                                 width)
            span.set_metadata(lanes=len(lanes))
            with _span("engine.dispatch"):
                toks, done, state = self._decode_h(
                    self.params, self._state(), jnp.asarray(token),
                    jnp.asarray(pos), refs, jnp.asarray(temp),
                    jnp.asarray(topk), jnp.asarray(seed), jnp.asarray(ctr),
                    jnp.asarray(eos), h, use_top_k, stochastic, use_eos)
            self._put_state(state)
            with _span("engine.sync"):
                rows = np.asarray(toks)
            done_rows = np.asarray(done)
            with _span("engine.finish"):
                for i, seq in enumerate(lanes):
                    # post-truncation: cut the lane at its first finish
                    # event — the device-computed eos mask, or a
                    # host-matched stop sequence (which may span the
                    # horizon boundary). Draws after the cut never
                    # entered the stream, so the host counter advances by
                    # the kept count only.
                    kept, reason = apply_finish(seq.sampler, seq.out,
                                                rows[i],
                                                eos_row=done_rows[i])
                    seq.sampler.skip(kept)   # host stream stays aligned
                    # the horizon wrote the fed tokens' KV at
                    # pos[i]..pos[i]+h-1, but only the first `kept`
                    # positions hold tokens the sequence keeps: prefilled
                    # tracks *valid* written KV.
                    seq.prefilled = int(pos[i]) + kept
                    self.decode_tokens += kept
                    self.truncated_tokens += h - kept
                    if reason is not None:
                        seq.finish_reason = reason
                        # reclaim the pre-extended horizon tail the lane
                        # will never write: pages return to the pool
                        # mid-step, so they fund this step's reap/admit
                        # instead of idling until the sequence is
                        # released.
                        self.reclaimed_pages += self.cache.truncate(
                            seq.seq_id, int(pos[i]) + kept)
            self.decode_dispatches += 1

    def _spec_step(self) -> bool:
        """One speculative decode round: draft K tokens per lane, score
        all K+1 positions in **one** ``verify_paged`` target dispatch,
        accept the longest draft prefix matching the pinned draws.

        Returns False when speculation does not apply this step — no
        spec config, a pending prefill (token-time must not run ahead
        of chunk-time, mirroring ``Scheduler.decode_horizon``'s rule),
        every lane's controller at K = 0, or no drafter proposal — and
        the caller falls through to the plain fused-horizon path.

        Accounting per lane (draft length k, verify width k+1):
        ``acc`` = accepted draft prefix; the emitted row is the pinned
        draws ``rows[:acc+1]`` (accepted tokens + correction/bonus);
        ``apply_finish`` cuts it at the first eos/stop event exactly as
        in the horizon path, the host counter advances by the kept
        count only, and ``truncate`` reclaims every page past the kept
        KV — the rejected tail — immediately.
        """
        if self.spec is None:
            return False
        batch = self.sched.decode_batch(self.decode_batch)
        if not batch or any(s.in_prefill for s in self.sched.running):
            return False
        ks = self.sched.spec_ks(batch, self.spec)
        if max(ks) == 0:
            self.spec_fallbacks += 1
            return False
        drafts = self.spec.drafter.propose(batch, ks)
        drafts = [[int(t) for t in d[:k]] for d, k in zip(drafts, ks)]
        if not any(drafts):
            self.spec_fallbacks += 1
            return False
        # pow2 verify width: C = K+1 compiles a handful of shapes
        kmax = 1 << (max(len(d) for d in drafts) - 1).bit_length()
        c = kmax + 1
        with _span("engine.verify", c=c) as span:
            with _span("engine.stage"):
                lanes: List[Tuple[Sequence, List[int]]] = []
                for seq, draft in zip(batch, drafts):
                    if seq not in self.sched.running:
                        continue         # preempted by an earlier lane
                    pos = seq.prompt_len + len(seq.out) - 1
                    # pre-extend for feed token + all drafts, like the
                    # horizon
                    copies = self.sched.ensure_tokens(seq, pos,
                                                      pos + 1 + len(draft))
                    if copies is None:
                        continue
                    self._apply_copies(copies)
                    lanes.append((seq, draft))
                assert all(s in self.sched.running for s, _ in lanes)
                if not lanes:
                    return True          # everything preempted this step
                d = self.decode_batch
                tokens = np.zeros((d, c), np.int32)
                q_start = np.zeros((d,), np.int32)
                # null lanes mirror the decode scan's self-absorbing
                # null-page lanes: one fake token written to (and read
                # from) page 0.
                n_valid = np.ones((d,), np.int32)
                temp = np.zeros((d,), np.float32)
                topk = np.zeros((d,), np.int32)
                seed = np.zeros((d,), np.uint32)
                ctr = np.zeros((d,), np.int32)
                seqs: List[Optional[Sequence]] = [None] * d
                for i, (seq, draft) in enumerate(lanes):
                    row = [seq.out[-1]] + draft
                    tokens[i, :len(row)] = row
                    q_start[i] = seq.prompt_len + len(seq.out) - 1
                    n_valid[i] = len(row)
                    s = seq.sampler
                    temp[i], topk[i], seed[i] = s.temperature, s.top_k, s.seed
                    ctr[i] = len(seq.out)
                    seqs[i] = seq
                refs = self._refs(seqs)
                use_top_k = any(s.sampler.top_k > 0 for s, _ in lanes)
                stochastic = any(s.sampler.temperature > 0
                                 for s, _ in lanes)
                widest = max(len(s.sampler.eos_ids) for s, _ in lanes)
                use_eos = widest > 0
                eos = np.full((d, 1), -1, np.int32)
                if use_eos:
                    width = 1 << (widest - 1).bit_length() if widest > 1 else 1
                    eos = np.full((d, width), -1, np.int32)
                    eos[:len(lanes)] = eos_table(
                        [s.sampler for s, _ in lanes], width)
            span.set_metadata(lanes=len(lanes))
            with _span("engine.dispatch"):
                pinned, done, state = self._verify(
                    self.params, self._state(), jnp.asarray(tokens),
                    jnp.asarray(q_start), jnp.asarray(n_valid), refs,
                    jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(seed),
                    jnp.asarray(ctr), jnp.asarray(eos), use_top_k,
                    stochastic, use_eos)
            self._put_state(state)
            with _span("engine.sync"):
                rows = np.asarray(pinned)
            done_rows = np.asarray(done)
            with _span("engine.finish"):
                for i, (seq, draft) in enumerate(lanes):
                    acc = 0
                    while acc < len(draft) and draft[acc] == rows[i, acc]:
                        acc += 1
                    kept, reason = apply_finish(
                        seq.sampler, seq.out, rows[i, :acc + 1],
                        eos_row=done_rows[i, :acc + 1])
                    seq.sampler.skip(kept)   # host stream stays aligned
                    pos = int(q_start[i])
                    seq.prefilled = pos + kept   # valid written KV only
                    self.decode_tokens += kept
                    self.truncated_tokens += 1 + len(draft) - kept
                    self.spec_proposed += len(draft)
                    self.spec_accepted += acc
                    self.sched.spec_feedback(seq, len(draft), acc,
                                             self.spec)
                    if reason is not None:
                        seq.finish_reason = reason
                    # rejected tails (and finish tails) hand their
                    # pre-extended pages back mid-step via the existing
                    # truncate path
                    self.reclaimed_pages += self.cache.truncate(
                        seq.seq_id, pos + kept)
        self.decode_dispatches += 1
        self.spec_dispatches += 1
        return True

    def _reap_done(self) -> None:
        with _span("engine.reap"):
            for seq in list(self.sched.running):
                if seq.done:
                    seq.finish_reason = seq.finish_reason or "length"
                    self.finish_reasons[seq.finish_reason] = (
                        self.finish_reasons.get(seq.finish_reason, 0) + 1)
                    self._finished[seq.seq_id] = seq.out
                    self.sched.finish(seq)

    def step(self) -> None:
        """One engine iteration: admit, one prefill chunk, one decode
        horizon (up to ``decode_horizon`` fused tokens per lane) for
        the running batch, reclaim finished sequences. Finished
        sequences are reaped right after prefill too, so their pages
        fund the decode batch's on-demand growth.

        The step itself enters the engine's mesh/rules context — not
        just ``generate()`` — so externally driven loops (AsyncEngine)
        trace sharded engines with the sharding constraints active.
        """
        meshctx, rulectx = _run_ctx(self.rules)
        with meshctx, rulectx, _span("engine.step", step=self.steps):
            with _span("engine.admit"):
                self.sched.admit()
            seq = self.sched.next_prefill()
            if seq is not None:
                self._prefill_step(seq)
            self._reap_done()
            if not self._spec_step():
                self._decode_step()
            self._reap_done()
            self.steps += 1

    # -- public API -----------------------------------------------------------

    def submit(self, request: Request) -> Sequence:
        """Validate and queue one request; returns the live Sequence
        handle (the async loop streams from it and cancels through
        it). ``Scheduler.submit`` is the single validation site."""
        if self.state_spec.cross_tokens and request.frames is None:
            raise ValueError(
                f"family {self.cfg.family!r} needs encoder frames on "
                "every request (Request.frames)")
        return self.sched.submit(
            request.prompt, request.max_new_tokens,
            sampler=sampler_for(request, self.cfg.vocab_size),
            frames=request.frames)

    def cancel(self, seq: Sequence) -> bool:
        """Cancel a submitted sequence — a finish event like any other:
        counted in ``stats()["finish_reasons"]``, pages released by the
        scheduler (running lanes reaped mid-trace, waiting ones just
        leave the queue). False if the sequence already finished."""
        if not self.sched.cancel(seq):
            return False
        self.finish_reasons["cancelled"] = (
            self.finish_reasons.get("cancelled", 0) + 1)
        return True

    def generate(self, requests: List[Request]) -> List[List[int]]:
        """Serve all requests to completion; outputs in request order."""
        # submit() is the single validation site; on failure, name the
        # offending request and unwind this wave's earlier submissions
        # so a never-fits request cannot strand them queued.
        order: List[int] = []
        for i, r in enumerate(requests):
            try:
                order.append(self.submit(r).seq_id)
            except ValueError as e:
                self.sched.abandon(order)
                raise ValueError(f"request {i}: {e}") from None
        meshctx, rulectx = _run_ctx(self.rules)
        with meshctx, rulectx:
            while self.sched.has_work:
                self.step()
        # pop (not read) so a long-lived engine doesn't accumulate every
        # past wave's outputs.
        return [self._finished.pop(sid) for sid in order]

    def stats(self) -> Dict[str, object]:
        """Serving counters: prefix-cache hits, COW/eviction/preemption
        activity, and pool occupancy."""
        c, s = self.cache, self.sched
        out = {
            # engine-level flag: for a slot-only family the page pool
            # reports False (it has no pages to share) while prefix
            # reuse still runs through the state-checkpoint cache.
            "prefix_cache": self.prefix_cache,
            "prefix_hit_rate": round(c.prefix_hit_rate(), 4),
            "prefix_hit_tokens": c.prefix_hit_tokens,
            "prefix_query_tokens": c.prefix_query_tokens,
            "cow_copies": c.cow_copies,
            "evictions": c.evictions,
            "preemptions": s.preemptions,
            "cached_blocks": c.cached_blocks,
            "free_blocks": c.free_blocks,
            "blocks_in_use": c.blocks_in_use,
            "peak_blocks_in_use": c.peak_blocks_in_use,
            "utilization": round(c.utilization(), 4),
            "admitted": s.admitted,
            "finished": s.finished,
            "cancelled": s.cancelled,
            "finish_reasons": dict(self.finish_reasons),
            "steps": self.steps,
            "decode_tokens": self.decode_tokens,
            "decode_dispatches": self.decode_dispatches,
            "tokens_per_dispatch": round(
                self.decode_tokens / max(self.decode_dispatches, 1), 3),
            "truncated_tokens": self.truncated_tokens,
            "reclaimed_pages": self.reclaimed_pages,
        }
        # total state footprint: live pages (all pools) + live slots —
        # the quantity admission/preemption actually manage. For a
        # recurrent family this is O(1) per sequence by construction.
        per_page = sum(
            int(np.prod((p.shape[0],) + p.shape[2:])) * p.dtype.itemsize
            for p in c.pools.values())
        foot = c.blocks_in_use * per_page
        if self.slot_pool is not None:
            sp = self.slot_pool
            foot += sp.slots_in_use * sp.bytes_per_slot
            out.update({
                "state_slots_in_use": sp.slots_in_use,
                "free_state_slots": sp.free_slots,
                "peak_state_slots_in_use": sp.peak_slots_in_use,
                "state_bytes_per_slot": sp.bytes_per_slot,
            })
            if self.ckpts is not None:
                cs = self.ckpts.stats()
                out["state_checkpoints"] = cs["entries"]
                out["checkpoint_hit_tokens"] = cs["hit_tokens"]
        out["state_footprint_bytes"] = int(foot)
        if self.spec is not None:
            # accepted tokens per *target* dispatch is exactly
            # tokens_per_dispatch under speculation (verify dispatches
            # count as decode dispatches and only kept tokens count),
            # named for what it measures: the spec-decode win.
            out.update({
                "spec_dispatches": self.spec_dispatches,
                "spec_proposed_tokens": self.spec_proposed,
                "spec_accepted_tokens": self.spec_accepted,
                "spec_fallback_steps": self.spec_fallbacks,
                "acceptance_rate": round(
                    self.spec_accepted / max(self.spec_proposed, 1), 4),
                "accepted_tokens_per_target_dispatch": round(
                    self.decode_tokens
                    / max(self.decode_dispatches, 1), 3),
            })
        return out

    def reset_stats(self) -> None:
        """Zero the serving counters (cached pages stay resident)."""
        self.cache.reset_stats()
        if self.slot_pool is not None:
            self.slot_pool.reset_stats()
        self.sched.preemptions = 0
        self.sched.admitted = 0
        self.sched.finished = 0
        self.sched.cancelled = 0
        self.steps = 0
        self.decode_tokens = 0
        self.decode_dispatches = 0
        self.truncated_tokens = 0
        self.reclaimed_pages = 0
        self.spec_dispatches = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_fallbacks = 0
        self.finish_reasons = {}


class Engine:
    def __init__(self, cfg: ArchConfig, params, *, batch_size: int = 4,
                 max_len: int = 256, rules: Optional[R.Rules] = None):
        if cfg.family not in ("dense", "moe", "ssm", "hybrid"):
            raise ValueError(f"Engine serves LM families, got {cfg.family}")
        self.cfg = cfg
        if cfg.quant.weights and cfg.family == "dense":
            params = R.quantize_params(params)
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.rules = rules
        self.model = api.get_model(cfg)
        # attention-cache families thread per-lane positions through
        # prefill/decode so left-padded lanes mask their pad columns out
        # of every key set; recurrent families (ssm/hybrid) keep the
        # legacy shared positions.
        self._lane_pos = cfg.family in ("dense", "moe")
        # why each request of the last generate() call stopped,
        # parallel to its returned outputs
        self.finish_reasons: List[str] = []

        def _decode(params, cache, token, pos, write_pos):
            if self._lane_pos:
                return self.model.decode_step(params, cache, token, pos,
                                              cfg, write_pos=write_pos)
            return self.model.decode_step(params, cache, token, write_pos,
                                          cfg)

        def _prefill_one(params, tokens, n_pad):
            if self._lane_pos:
                return self.model.prefill(params, tokens, cfg, max_len,
                                          n_pad=n_pad)
            return self.model.prefill(params, tokens, cfg, max_len)

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._prefill = jax.jit(_prefill_one)

    def generate(self, requests: List[Request]) -> List[List[int]]:
        """Serve all requests (batched, prompt lengths padded per batch).

        ``finish_reasons`` (parallel to the returned outputs) records
        why each request stopped: ``"eos"`` / ``"stop"`` on a finish
        event, ``"length"`` when the token budget ran out.
        """
        meshctx, rulectx = _run_ctx(self.rules)
        outs: List[List[int]] = []
        self.finish_reasons = []
        with meshctx, rulectx:
            for i in range(0, len(requests), self.batch):
                chunk = requests[i:i + self.batch]
                res, reasons = self._generate_batch(chunk)
                outs.extend(res)
                self.finish_reasons.extend(reasons)
        return outs

    def _generate_batch(self, chunk: List[Request]
                        ) -> Tuple[List[List[int]], List[str]]:
        """One padded batch. The final ragged chunk of a trace is padded
        up to ``batch_size`` with masked lanes (zero prompt, zero token
        budget) so the batch dimension — and with it the compiled
        prefill/decode shapes — never varies across chunks: one compile
        per prompt length serves the whole trace instead of one per
        ragged tail (the PR 3 bench-warmup artifact's root cause).

        Finished lanes — budget met, eos/stop fired, or padding — are
        **masked**: they feed the constant token 0 and their sampler is
        never consulted again, so a finished lane cannot perturb batch
        stats or RNG accounting (each lane's attention and counter-keyed
        sampling stream are independent of the others, so in exact mode
        the real lanes' tokens are bit-identical to a run where every
        lane stays live — pinned by the mixed-length batch test). When
        every real lane has finished, the decode loop exits early
        instead of burning steps feeding masked lanes.
        """
        real = len(chunk)
        pad = Request(prompt=np.zeros(1, np.int32), max_new_tokens=0)
        chunk = chunk + [pad] * (self.batch - real)
        b = len(chunk)
        samplers = [sampler_for(r, self.cfg.vocab_size) for r in chunk]
        plen = max(len(r.prompt) for r in chunk)
        toks = np.zeros((b, plen), np.int32)
        n_pad = np.zeros((b,), np.int32)
        for j, r in enumerate(chunk):
            toks[j, plen - len(r.prompt):] = r.prompt  # left-pad
            n_pad[j] = plen - len(r.prompt)
        # per-lane pad counts: pad columns are masked out of every key
        # set and RoPE runs on local positions, so a short prompt in a
        # mixed-length batch computes exactly what it would alone.
        logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                      jnp.asarray(n_pad))
        # whole-array d2h then host slicing (guard-safe: device-side
        # basic indexing transfers the index scalars h2d).
        rows = np.asarray(logits)[:, -1]
        results: List[List[int]] = [[] for _ in range(b)]
        reasons: List[Optional[str]] = [None] * b
        for j in range(b):
            if j < real:
                _, reasons[j] = apply_finish(samplers[j], results[j],
                                             [samplers[j](rows[j])])

        def live(j: int) -> bool:
            return (j < real and reasons[j] is None
                    and len(results[j]) < chunk[j].max_new_tokens)

        token = jnp.asarray(np.array(
            [results[j][-1] if live(j) else 0 for j in range(b)], np.int32))
        max_new = max(r.max_new_tokens for r in chunk)
        pos = plen                       # shared physical write column
        for _ in range(max_new - 1):
            if not any(live(j) for j in range(b)):
                break                    # early exit: all lanes finished
            # per-lane logical positions (pad-corrected); the write slot
            # stays the shared physical column.
            lane_pos = (jnp.asarray(pos - n_pad, jnp.int32)
                        if self._lane_pos else None)
            logits, cache = self._decode(self.params, cache, token,
                                         lane_pos,
                                         jnp.asarray(pos, jnp.int32))
            rows = np.asarray(logits)
            nxt = np.zeros((b,), np.int32)
            for j in range(b):
                if live(j):
                    _, reasons[j] = apply_finish(
                        samplers[j], results[j], [samplers[j](rows[j])])
                    if live(j):
                        nxt[j] = results[j][-1]
            token = jnp.asarray(nxt)
            pos += 1
        return (results[:real],
                [r or "length" for r in reasons[:real]])
