"""Dynamic SplitFuse serving scheduler.

The policy layer the reference keeps in MII above ``InferenceEngineV2``
(engine mechanism: ``put``/``decode``/``can_schedule``/``flush``; policy:
the DeepSpeed-FastGen Dynamic SplitFuse composition,
``blogs/deepspeed-fastgen/README.md`` "Dynamic SplitFuse" — every forward
carries a bounded token budget filled with all runnable DECODE steps first,
then chunks of pending prefills, so long prompts never stall decode latency
and the batch shape stays in a narrow, compiled-bucket-friendly band).

Design points beyond the happy path:
- admission RESERVES capacity for a request's whole lifetime (full prompt +
  max_new_tokens worth of KV blocks), so a request that is admitted can
  always run to completion — no mid-run KV exhaustion can strand the batch;
- when the queue drains to pure decode, the loop switches to the engine's
  multi-step on-device ``decode`` (one host round-trip per horizon instead
  of per token — the steady-state fast path);
- nothing is dropped silently: un-runnable work raises with the stalled
  uids named, and partial generations stay readable via ``results``;
- a model that generates by masked diffusion over blocks (``diffusion_block_size``
  ``B``) emits no token from its prefill and none a step: its prompt's whole
  blocks are prefilled in chunks that end on block boundaries, the rest of
  the prompt opens the first generated block (the ``first_tokens`` of the
  row's first burst), and its rows advance ``B`` tokens at a time in bursts
  whose horizon is a power of two of BLOCKS (``_block_burst``); they ride in
  no ``put`` beside prefill chunks, so while prompts wait the chunks go first
  and the rows stand.
"""

import copy
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from ...monitor.trace import NULL_SPAN, get_tracer
from .scheduling_utils import SchedulingResult


class _Request:
    __slots__ = ("uid", "prompt", "max_new_tokens", "eos_token_id", "fed", "generated", "done",
                 "charged_blocks", "shared_blocks", "sampling", "tenant", "t_submit", "prefill_len")

    def __init__(self, uid, prompt, max_new_tokens, eos_token_id, sampling=None,
                 tenant=None, block: int = 0):
        self.uid = uid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        # prompt tokens that go through prefill: all of them, or the whole blocks
        # of a block-diffusion model (the rest opens the first generated block)
        self.prefill_len = self.prompt.size // block * block if block else self.prompt.size
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.sampling = sampling  # SamplingParams | None (= greedy)
        self.tenant = tenant      # owner identity (serving metering); None = untenanted
        self.fed = 0          # prompt tokens already given to the engine
        self.generated: List[int] = []
        self.done = False
        self.charged_blocks = 0  # lifetime KV reservation charged at admission
        self.shared_blocks = 0   # blocks arriving shared from the prefix cache
        self.t_submit = time.perf_counter()  # -> first_wait_ms of the step that first feeds it

    @property
    def sampled(self) -> bool:
        return self.sampling is not None and not self.sampling.greedy

    @property
    def prefilling(self) -> bool:
        return self.fed < self.prefill_len

    @property
    def total_tokens(self) -> int:
        return self.prompt.size + self.max_new_tokens


class DynamicSplitFuseScheduler:
    """Continuous-batching loop over :class:`InferenceEngineV2`.

    ``token_budget`` bounds the tokens per forward (clamped to the engine's
    ``max_ragged_batch_size``; must be positive). ``submit`` enqueues
    requests; ``step`` runs one composed forward; ``run`` drives to
    completion and returns ``{uid: generated token list}``.
    """

    DECODE_HORIZON = 32  # max on-device steps per multi-step decode call

    def __init__(self, engine, token_budget: Optional[int] = None, speculative=None,
                 drafter=None):
        self.engine = engine
        sm = engine.config.state_manager
        if token_budget is None:
            token_budget = sm.max_ragged_batch_size
        if token_budget <= 0:
            raise ValueError(f"token_budget must be positive, got {token_budget}")
        self.token_budget = min(int(token_budget), sm.max_ragged_batch_size)
        self.max_seqs = sm.max_ragged_sequence_count
        # block length of a model generated by masked diffusion over blocks; 0: causal
        self._block = int(getattr(engine.model_config, "diffusion_block_size", 0) or 0)
        self._pending: List[_Request] = []   # not yet tracked by the engine
        self._active: Dict[int, _Request] = {}
        self._results: Dict[int, List[int]] = {}
        self._reserved_blocks = 0  # KV blocks promised to active requests
        # serving-plane accounting the prefix-cache A/B reads: prompt tokens
        # actually computed vs skipped via radix hits (exact — counted at the
        # feed site, not inferred from latency)
        self.stats = {"prefill_tokens_fed": 0, "prefill_tokens_skipped": 0}
        # speculative decoding: ``speculative`` overrides the engine's
        # ``ragged.speculative`` block; ``drafter`` overrides the drafter
        # built from it (tests/benches inject oracle/junk drafters). With
        # the block absent/off, NO drafter object exists and every step
        # path below is byte-identical to the pre-speculation scheduler
        # (test-enforced zero overhead).
        self._spec = speculative if speculative is not None \
            else getattr(engine.config, "speculative", None)
        if self._spec is not None and not getattr(self._spec, "enabled", False):
            self._spec = None
        self._drafter = drafter
        if self._drafter is None and self._spec is not None:
            from .speculative import build_drafter
            self._drafter = build_drafter(self._spec)
        if self._drafter is not None and self._spec is None:
            from .config_v2 import SpeculativeConfig
            self._spec = SpeculativeConfig(mode="ngram")  # injected drafter, default k
        if self._block and self._drafter is not None:
            raise NotImplementedError(
                f"speculative decoding of a model with diffusion_block_size={self._block}: a draft is verified "
                "one causal token at a time, and this model has no causal next token")
        self.spec_stats = {"rounds": 0, "drafted": 0, "accepted": 0, "rejected": 0,
                           "backoffs": 0}
        self._spec_by_uid: Dict[int, Dict[str, int]] = {}
        # spec-burst backoff: consecutive zero-accept verify rounds per uid
        # (a hopeless drafter must stop burning k+1 verify tokens per round
        # forever); past `backoff_after` the uid stops drafting and rides
        # the plain decode burst, re-probed every `reprobe_every` rounds
        self._spec_zero: Dict[int, int] = {}
        # incremental prompt+generated context per speculating uid: generated
        # only ever APPENDS for a live request, so each round copies just the
        # delta instead of re-concatenating the whole stream (O(new tokens),
        # not O(context), in the hottest serving loop)
        self._spec_ctx: Dict[int, np.ndarray] = {}
        # optional per-step observer, `fn(uids, chunk_sizes, t0, dur, kind)`
        # after EVERY engine forward this scheduler composes — `kind` is
        # "put" (mixed decode+prefill chunks, chunk_sizes = the tokens fed a
        # row), "decode" (a burst, causal or of diffusion blocks) or
        # "spec_verify" (the speculative verify forward); for both bursts
        # chunk_sizes = the tokens each row EMITTED, the tail past a stop
        # left out, so that a rate over them is a rate of tokens delivered.
        # The serving replica attaches one to attribute forward wall time
        # to the requests whose chunks composed it (per-chunk prefill spans
        # + per-tenant compute-second apportionment). None (the default)
        # adds zero work on every path.
        self.step_observer = None

    def submit(self, uid: int, prompt, max_new_tokens: int = 32, eos_token_id=None,
               sampling=None, tenant=None):
        if uid in self._active or any(r.uid == uid for r in self._pending):
            raise ValueError(f"uid {uid} already queued")
        if sampling is not None:
            sampling.validate()  # raises ValueError on out-of-range knobs
        req = _Request(uid, prompt, max_new_tokens, eos_token_id, sampling=sampling,
                       tenant=tenant, block=self._block)
        if self._block and req.sampled:
            raise NotImplementedError(
                f"uid {uid}: temperature sampling of a model with diffusion_block_size={self._block}: a block's "
                "positions are unmasked by the confidence of their argmax, and no draw is carried through it")
        if req.prompt.size == 0:
            raise ValueError(f"uid {uid}: empty prompt")
        if req.max_new_tokens <= 0:
            raise ValueError(f"uid {uid}: max_new_tokens must be positive, "
                             f"got {req.max_new_tokens}")
        if req.total_tokens > self.engine._max_context:
            raise ValueError(f"uid {uid}: prompt {req.prompt.size} + max_new_tokens "
                             f"{req.max_new_tokens} exceeds the engine max_context "
                             f"{self.engine._max_context}")
        self._pending.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self._pending or self._active)

    @property
    def finished(self):
        """Uids whose generation is complete (eos or max_new_tokens) — load
        harnesses poll this after each ``step`` to stamp completion times."""
        return frozenset(self._results)

    @property
    def results(self) -> Dict[int, List[int]]:
        """Generations so far — finished requests complete, active partial."""
        out = dict(self._results)
        for uid, req in self._active.items():
            out[uid] = list(req.generated)
        return out

    def discard_result(self, uid: int) -> None:
        """Drop a FINISHED request's stored generation (and its ``finished``
        membership). The serving gateway streams tokens out incrementally
        and reads ``results`` every step — without discarding, a long-lived
        scheduler's result dict (and each per-step copy) grows with every
        request ever served. No-op for unknown/active uids."""
        self._results.pop(uid, None)
        self._spec_by_uid.pop(uid, None)

    @property
    def speculating(self) -> bool:
        """True when a drafter is wired in (``ragged.speculative`` present)."""
        return self._drafter is not None

    def spec_summary(self, uid: int) -> Optional[Dict[str, int]]:
        """Per-request speculation accounting (``{"drafted", "accepted"}``)
        for an active/finished uid, until ``discard_result``; None when the
        request never speculated. The gateway's request summary record
        carries the derived acceptance rate."""
        return self._spec_by_uid.get(uid)

    def spec_params(self) -> Optional[Dict[str, int]]:
        """The live speculative knobs (``{"k", "tree_width"}``), None when
        this scheduler is not speculating. The serving control plane reads
        this before proposing a K adaptation."""
        if self._spec is None:
            return None
        return {"k": int(self._spec.k),
                "tree_width": int(getattr(self._spec, "tree_width", 1))}

    def set_spec_params(self, k: Optional[int] = None,
                        tree_width: Optional[int] = None) -> Optional[Dict[str, int]]:
        """Retarget speculative K / tree width for FUTURE draft rounds.
        ``_spec`` may alias ``engine.config.speculative`` (shared with other
        schedulers built from the same config), so the update REPLACES the
        config object rather than mutating it in place. ``_spec_burst``
        re-reads ``self._spec`` every round, so the new knobs apply from the
        next round with no re-plumbing. No-op (returns None) when not
        speculating; returns the applied params otherwise."""
        if self._spec is None:
            return None
        kwargs = {}
        if k is not None:
            kwargs["k"] = max(1, int(k))
        if tree_width is not None:
            kwargs["tree_width"] = max(1, int(tree_width))
        if kwargs:
            try:
                self._spec = dataclasses.replace(self._spec, **kwargs)
            except TypeError:  # injected non-dataclass spec stub (tests)
                sp = copy.copy(self._spec)
                for name, v in kwargs.items():
                    setattr(sp, name, v)
                self._spec = sp
        return self.spec_params()

    def new_tokens(self, uid: int, start: int) -> List[int]:
        """Tokens generated past position ``start`` for a pending/active/
        finished uid — the gateway's per-step fan-out read. Copies only the
        TAIL, where ``results`` would copy every active generation whole
        each step (O(total tokens) per step, quadratic over a request's
        life). Unknown uids yield []."""
        req = self._active.get(uid)
        gen = req.generated if req is not None else self._results.get(uid)
        return [] if gen is None else list(gen[start:])

    def cancel(self, uid: int) -> bool:
        """Abort a request NOW: a pending one is dropped, an active one is
        finished in place (engine sequence flushed, lifetime KV reservation
        released, tokens-so-far kept in ``results``). The serving gateway
        calls this when a client times out or disconnects — without it an
        abandoned request would keep decoding to ``max_new_tokens``,
        holding its KV blocks and an admission slot against live traffic.
        MUST be called from the thread that drives ``step`` (it mutates
        scheduler/engine state). Returns False for unknown uids."""
        for i, req in enumerate(self._pending):
            if req.uid == uid:
                self._pending.pop(i)
                self._results[uid] = req.generated  # partial = empty, kept
                return True
        req = self._active.get(uid)
        if req is None:
            return False
        self._finish(req)
        return True

    def _blocks_for(self, n_tokens: int) -> int:
        bs = self.engine.config.kv_block_size
        return -(-n_tokens // bs)

    def _finish(self, req: _Request):
        req.done = True
        seq = self.engine.state_manager.get_sequence(req.uid)
        if seq is not None:
            # decode/speculate horizons reserve and materialize KV past the
            # last token an early-finished (eos) or cancelled request keeps.
            # Rewind the overshoot through the single rollback helper BEFORE
            # flush: flush publishes completed full blocks into the radix
            # tree, and without the rewind the tree would take references on
            # blocks keyed by post-eos garbage tokens — blocks that then
            # never return to the free list until LRU pressure evicts them.
            known = req.fed + max(0, len(req.generated) - 1)
            if self._block:
                # committed blocks alone: the K/V of a block saw the whole block, so one
                # that the request keeps a part of is not kept at all
                known = (req.prompt.size + len(req.generated)) // self._block * self._block
            if seq.seen_tokens > known:
                # final=True: the flush below is this sequence's last act, so
                # the COW guard (which could need a block from a dry pool)
                # is skipped — a terminal rewind must never be able to fail
                self.engine.state_manager.rollback_to(seq, known, final=True)
        if self._drafter is not None:
            self._drafter.finish(req.uid)
            self._spec_ctx.pop(req.uid, None)
            self._spec_zero.pop(req.uid, None)
        self.engine.flush(req.uid)
        self._reserved_blocks -= req.charged_blocks
        self._active.pop(req.uid, None)
        self._results[req.uid] = req.generated

    def _try_admit(self, req: _Request, batch_uids: List[int], batch_lengths: List[int],
                   budget: int) -> bool:
        """Admission reserves the request's WHOLE lifetime: full-prompt KV
        blocks + generation headroom, so an admitted request can always run
        to completion regardless of later arrivals. Validation is CUMULATIVE
        — the engine sees the whole batch composed so far plus this request,
        so a combination that passes here can never be rejected by the
        final ``put(do_checks=True)`` after state was already mutated.

        Prefix-cache admission order: PROBE first (a pure lookup — a refused
        request must leave the tree, its LRU clock, and the hit stats
        untouched, and must not burn a COW copy), budget-check against only
        the UNCACHED remainder — cached prompt tokens hit neither the token
        budget (the first chunk starts after the hit) nor the block budget
        (shared blocks are already resident) — then ACQUIRE once admission
        is certain. Nothing mutates between probe and acquire (single
        thread), so the acquisition realizes exactly the probed hit."""
        if len(batch_uids) >= self.max_seqs:
            return False
        sm = self.engine.config.state_manager
        if self.engine.state_manager.n_tracked_sequences >= sm.max_tracked_sequences:
            return False  # acquisition would raise, not refuse
        n_cached, shared, tree_only, match = self.engine.probe_prefix(req.prompt)
        need = self._blocks_for(req.total_tokens) - shared
        first = self._chunk(budget, req.prefill_len - n_cached)
        if first <= 0 and (not self._block or req.prefill_len > n_cached):
            return False  # (a prompt shorter than a diffusion block has nothing to prefill)
        # supply side: the hit's tree-only shared blocks stop being evictable
        # the moment acquisition pins them — counting them as reclaimable
        # WHILE ALSO subtracting them from demand (`need`) would credit the
        # same blocks twice and over-admit by up to `shared`
        supply = self.engine.available_blocks - tree_only + self._owned_blocks()
        if self._reserved_blocks + need > supply:
            return False
        if self.engine.can_schedule(batch_uids + [req.uid],
                                    batch_lengths + [first]) is not SchedulingResult.Success:
            return False
        n_cached, shared = self.engine.acquire_prefix(req.uid, req.prompt, match=match,
                                                      tenant=req.tenant)
        req.fed = n_cached
        req.charged_blocks = self._blocks_for(req.total_tokens) - shared
        req.shared_blocks = shared
        self._reserved_blocks += req.charged_blocks
        self.stats["prefill_tokens_skipped"] += n_cached
        self._active[req.uid] = req
        return True

    def _chunk(self, budget: int, remaining: int) -> int:
        """Prompt tokens the next chunk of a row takes of ``remaining``: what
        the budget leaves, cut to whole blocks for a block-diffusion model."""
        take = min(budget, remaining)
        return take // self._block * self._block if self._block else take

    def _owned_blocks(self) -> int:
        """Blocks active sequences allocated THEMSELVES (shared radix-tree
        blocks excluded: they were never charged against the reservation)."""
        sm = self.engine.state_manager
        return sum(max(0, s.cur_allocated_blocks - s.shared_blocks)
                   for s in (sm.get_sequence(u) for u in self._active) if s is not None)

    def _append_token(self, req: _Request, tok: int) -> None:
        req.generated.append(tok)
        hit_eos = req.eos_token_id is not None and tok == req.eos_token_id
        if len(req.generated) >= req.max_new_tokens or hit_eos:
            self._finish(req)

    def _decode_burst(self, decoding: List[_Request]) -> int:
        """Pure-decode steady state: the engine's multi-step on-device scan
        (one host round-trip per horizon instead of per token). The horizon
        quantizes DOWN to a power of two: the engine compiles one program
        per exact n_steps, so free-running horizons would pay a fresh XLA
        compile for every distinct remaining-token count. A row keeps its
        horizon of tokens unless it stops inside the burst; the observer is
        told what each row kept."""
        horizon = min(min(r.max_new_tokens - len(r.generated) for r in decoding),
                      self.DECODE_HORIZON)
        horizon = 1 << (horizon.bit_length() - 1)  # 1,2,4,...,32: <=6 programs per bucket
        uids = [r.uid for r in decoding]
        first = [np.asarray([r.generated[-1]], np.int32) for r in decoding]
        # per-request eos rides down so the engine rewinds a mid-scan eos hit's
        # horizon overshoot before publishing (post-eos KV never enters the tree)
        eos = [r.eos_token_id for r in decoding]
        # sampling rides down only when some row actually samples — an
        # all-greedy burst keeps the original argmax scan program
        samp = [r.sampling for r in decoding] if any(r.sampled for r in decoding) else None
        # a replica that warmed FEWER horizons than the six (each is a program of the whole model: seconds of its
        # start-up) is served by the longest one it has; with all six warmed this is the horizon above
        horizon = self.engine.compiled_horizon(len(decoding), horizon, sampled=samp is not None)
        t0 = time.perf_counter()
        toks = np.asarray(self.engine.decode(uids, first, horizon, eos_token_ids=eos,
                                             sampling=samp))  # [S, horizon]
        self._emit(decoding, toks, t0, time.perf_counter() - t0)
        return len(decoding) * horizon

    def _emit(self, rows: List[_Request], new_tokens, t0: float, dur: float) -> int:
        """Append a burst's tokens row by row, dropping a row's tail past its
        stop (eos, or max_new_tokens inside the burst), and tell the observer
        what each row kept. Returns the tokens kept."""
        uids, emitted = [r.uid for r in rows], []
        for req, new in zip(rows, new_tokens):
            before = len(req.generated)
            for tok in np.asarray(new).tolist():
                self._append_token(req, int(tok))
                if req.done:
                    break
            emitted.append(len(req.generated) - before)
        if self.step_observer is not None:
            self.step_observer(uids, emitted, t0, dur, "decode")
        return sum(emitted)

    def _block_burst(self, decoding: List[_Request]) -> int:
        """A burst of a block-diffusion model: every row advances the same
        number of whole blocks in ONE engine call, a power of two of them
        (one program a (row bucket, blocks) pair) and no more than the row
        nearest its end still needs, the open block of a first burst
        included: the K/V the burst writes then never passes a row's lifetime
        reservation. A block's tokens arrive together; a row whose
        ``max_new_tokens`` or eos falls inside a block drops that block's
        tail, here and in the engine's committed length."""
        B = self._block
        # a row's first burst opens with the rest of its prompt (fewer than a block of tokens)
        known = [r.prompt[r.prefill_len:] if not r.generated else r.prompt[:0] for r in decoding]
        opened = [k.size for k in known]
        left = [r.max_new_tokens - len(r.generated) for r in decoding]
        blocks = min(min(-(-(n + o) // B) for n, o in zip(left, opened)), max(1, self.DECODE_HORIZON // B))
        blocks = 1 << (blocks.bit_length() - 1)
        uids = [r.uid for r in decoding]
        t0 = time.perf_counter()
        toks = np.asarray(self.engine.decode(uids, known, blocks * B, max_new_tokens=left,
                                             eos_token_ids=[r.eos_token_id for r in decoding]))
        # a row's open tokens are its prompt's: they lead the first block and are not new
        return self._emit(decoding, [row[o:] for row, o in zip(toks, opened)], t0, time.perf_counter() - t0)

    def _spec_context(self, req: _Request) -> np.ndarray:
        """The request's committed stream (prompt + generated) as one int32
        array, sized once for the request's whole lifetime and extended by
        only the NEW generated tokens each round (generated never shrinks
        for a live request). Returns a view of the filled region."""
        n = req.prompt.size + len(req.generated)
        entry = self._spec_ctx.get(req.uid)
        if entry is None:
            buf = np.empty(req.prompt.size + req.max_new_tokens, np.int32)
            buf[:req.prompt.size] = req.prompt
            filled = req.prompt.size
        else:
            buf, filled = entry
        if filled < n:
            buf[filled:n] = req.generated[filled - req.prompt.size:]
            filled = n
        self._spec_ctx[req.uid] = (buf, filled)
        return buf[:n]

    def _spec_not_drafting(self, uid: int) -> bool:
        """Backoff decision for one uid, advancing its counter while parked:
        past ``backoff_after`` consecutive zero-accept rounds a request
        stops drafting; every ``reprobe_every`` parked rounds one probe
        round drafts again (a stream that turned repetitive gets its
        speculation back), and any accepted token resets the counter."""
        n = getattr(self._spec, "backoff_after", 0)
        z = self._spec_zero.get(uid, 0)
        if not n or z < n:
            return False
        m = max(1, getattr(self._spec, "reprobe_every", 32))
        if (z - n) % m == m - 1:
            return False  # probe round
        self._spec_zero[uid] = z + 1  # parked round consumed
        return True

    def _spec_burst(self, decoding: List[_Request]) -> int:
        """Speculative steady state: draft up to K tokens (or a
        ``tree_width``-branch token tree) per sequence, then ONE batched
        verify forward commits the deepest target-agreeing path per
        sequence (plus a bonus token) and rolls rejected KV back. Returns
        committed tokens, or 0 when this round cannot speculate — the
        caller then falls back to the plain multi-step decode burst
        (drafters came up empty / everyone is backed off, a sequence is too
        close to max_context, or the transient KV demand exceeds what the
        pool can cover). Sampled requests force linear drafts (tree
        verification is greedy-only; the rejection-sampling verify keeps
        the output distribution exact). Backed-off requests skip drafting
        and decode alongside in a separate burst."""
        k = self._spec.k
        eng = self.engine
        width = max(1, int(getattr(self._spec, "tree_width", 1)))
        if any(r.sampled for r in decoding):
            width = 1
        drafting = [r for r in decoding if not self._spec_not_drafting(r.uid)]
        if not drafting:
            return 0
        # cheap pre-draft feasibility: if not even a LINEAR round fits the
        # token budget, don't pay the O(context) drafter scans every loop
        # (the PR 9 guard; the width-dependent check below re-validates with
        # this round's actual branch shapes)
        if len(drafting) * (k + 1) > min(self.token_budget,
                                         eng.config.state_manager.max_ragged_batch_size):
            return 0
        items = [(r.uid, self._spec_context(r)) for r in drafting]
        dmap = self._drafter.draft_branches_many(items, k, width)
        branches: Dict[int, List[np.ndarray]] = {}
        for r in drafting:
            bl = [np.asarray(b, np.int32).reshape(-1)[:k] for b in dmap.get(r.uid, ())]
            branches[r.uid] = [b for b in bl if b.size][:width]
        spec_reqs = [r for r in drafting if branches[r.uid]]
        if not spec_reqs:
            return 0
        # verify-chunk shape: root + W branches of the CONFIGURED k (the
        # engine pads short drafts) — keying the compiled program on this
        # round's actual max draft length would recompile the verify
        # forward every time the drafter's match length fluctuated; wmax
        # still varies, but it is bounded by tree_width (<= width programs
        # per bucket, vs k*width)
        wmax = max(len(branches[r.uid]) for r in spec_reqs)
        n_new = 1 + wmax * k
        if len(spec_reqs) * n_new > min(self.token_budget,
                                        eng.config.state_manager.max_ragged_batch_size):
            return 0
        seqs = []
        for r in spec_reqs:
            seq = eng.state_manager.get_sequence(r.uid)
            if seq is None or seq.seen_tokens + n_new > eng.max_context:
                return 0
            seqs.append(seq)
        # the verify chunk may transiently need blocks beyond the request's
        # lifetime reservation (near its final tokens): refuse up front
        # rather than strand the composed batch mid-run
        if sum(s.blocks_needed(n_new) for s in seqs) > eng.available_blocks:
            return 0
        uids = [r.uid for r in spec_reqs]
        firsts = [np.asarray([r.generated[-1]], np.int32) for r in spec_reqs]
        samp = [r.sampling for r in spec_reqs] if any(r.sampled for r in spec_reqs) else None
        # per-request eos rides down (decode()'s contract): an eos inside
        # the accepted run truncates the commit there, so the tree never
        # receives post-eos paths even when acceptance carries past it
        spec_drafts = [branches[r.uid] if len(branches[r.uid]) > 1 else branches[r.uid][0]
                       for r in spec_reqs]
        spec_eos = [r.eos_token_id for r in spec_reqs]
        if self.step_observer is None:
            outs = eng.speculate_decode(uids, firsts, spec_drafts, k,
                                        eos_token_ids=spec_eos, sampling=samp)
        else:
            t0 = time.perf_counter()
            outs = eng.speculate_decode(uids, firsts, spec_drafts, k,
                                        eos_token_ids=spec_eos, sampling=samp)
            self.step_observer(uids, [n_new] * len(uids), t0,
                               time.perf_counter() - t0, "spec_verify")
        self.spec_stats["rounds"] += 1
        backoff_n = getattr(self._spec, "backoff_after", 0)
        committed = 0
        for req, new in zip(spec_reqs, outs):
            bl = branches[req.uid]
            drafted_n = sum(int(b.size) for b in bl)
            a = len(new) - 1  # accepted positions (pads included)
            acc = min(a, max(int(b.size) for b in bl))
            self.spec_stats["drafted"] += drafted_n
            self.spec_stats["accepted"] += acc
            self.spec_stats["rejected"] += drafted_n - acc
            rec = self._spec_by_uid.setdefault(req.uid, {"drafted": 0, "accepted": 0})
            rec["drafted"] += drafted_n
            rec["accepted"] += acc
            if acc > 0:
                self._spec_zero.pop(req.uid, None)
            else:
                z = self._spec_zero.get(req.uid, 0) + 1
                self._spec_zero[req.uid] = z
                if backoff_n and z == backoff_n:
                    # entering backoff: this drafter stops paying verify
                    # FLOPs for a stream it keeps missing
                    self.spec_stats["backoffs"] += 1
                    from ...monitor.metrics import get_metrics

                    get_metrics().counter("serving/spec_disabled_total").inc()
            committed += len(new)
            for tok in new:
                self._append_token(req, int(tok))
                if req.done:
                    break  # eos/max_new inside the burst: _finish rewound the rest
        # requests that sat this round out (backed off / empty drafts) still
        # make progress: one plain multi-step burst alongside the verify
        resting = [r for r in decoding if r.uid not in {q.uid for q in spec_reqs}
                   and not r.done]
        if resting:
            committed += self._decode_burst(resting)
        return committed

    def step(self) -> int:
        """Compose and run ONE engine call: all runnable decodes first, then
        prefill chunks up to the token budget. Returns tokens processed
        (0 = nothing runnable). The whole of it is one ``serving/sched_step``
        span; its time outside the engine's child span is the scheduler's
        planning and token bookkeeping."""
        with get_tracer().span("serving/sched_step", tid="serving") as sp:
            return self._step(sp)

    def _burst(self, sp, decoding: List[_Request]) -> int:
        """Every decoding row in one engine call of several steps (blocks, for
        a block-diffusion model; a speculative round where a drafter is wired
        in), with no prefill chunk beside them."""
        if self._block:
            kind, n = "diffuse", self._block_burst(decoding)
        else:
            kind, n = "decode", 0
            if self._drafter is not None:
                kind, n = "spec_verify", self._spec_burst(decoding)
            if not n:
                kind, n = "decode", self._decode_burst(decoding)
        if sp is not NULL_SPAN:
            sp.set_args(kind=kind, rows=len(decoding), rows_decode=len(decoding), tokens=n,
                        prefill_tokens=0, pending=len(self._pending), active=len(self._active),
                        budget_left=self.token_budget, first_wait_ms=[])
        return n

    def _step(self, sp) -> int:
        decoding = [r for r in self._active.values() if not r.prefilling and not r.done]
        prefilling = [r for r in self._active.values() if r.prefilling]
        if decoding and not prefilling and not self._pending and len(decoding) <= self.max_seqs:
            return self._burst(sp, decoding)

        uids: List[int] = []
        chunks: List[np.ndarray] = []
        budget = self.token_budget

        # a causal row rides as one token beside the chunks; a diffusion row has no
        # token to feed: the chunks go alone and the rows' burst waits for them
        for req in () if self._block else decoding[:min(budget, self.max_seqs)]:
            uids.append(req.uid)
            chunks.append(np.asarray([req.generated[-1]], np.int32))
            budget -= 1
        n_decode = len(uids)

        def add_prefill(req):
            nonlocal budget
            take = self._chunk(budget, req.prefill_len - req.fed)
            if take <= 0 or len(uids) >= self.max_seqs:
                return False
            uids.append(req.uid)
            chunks.append(req.prompt[req.fed:req.fed + take])
            req.fed += take
            budget -= take
            self.stats["prefill_tokens_fed"] += take
            return True

        for req in prefilling:
            add_prefill(req)
        # FIFO-preferred admission with head-of-line skip-ahead: a pending
        # request that cannot be admitted (e.g. its lifetime KV reservation
        # exceeds what the pool can currently promise) must not starve later
        # pending requests that do fit — scan past it instead of breaking
        i = 0
        first_fed = len(uids)  # rows from here on are fed their first chunk
        while i < len(self._pending) and budget > 0 and len(uids) < self.max_seqs:
            req = self._pending[i]
            if self._try_admit(req, uids, [c.size for c in chunks], budget):
                self._pending.pop(i)
                add_prefill(req)
            else:
                i += 1

        if not uids and self._block:
            # no chunk could be composed (a prompt shorter than a block was admitted with
            # nothing to prefill, or what is pending does not fit yet): the rows go on
            decoding = [r for r in self._active.values() if not r.prefilling and not r.done][:self.max_seqs]
            if decoding:
                return self._burst(sp, decoding)
        if sp is not NULL_SPAN:
            now = time.perf_counter()
            n_tokens = sum(c.size for c in chunks)
            sp.set_args(kind="put" if uids else "none", rows=len(uids), rows_decode=n_decode,
                        tokens=n_tokens, prefill_tokens=n_tokens - n_decode,
                        pending=len(self._pending), active=len(self._active), budget_left=budget,
                        first_wait_ms=[round((now - self._active[u].t_submit) * 1e3, 3)
                                       for u in uids[first_fed:]])
        if not uids:
            return 0
        # sampling rides down only when a sampled row's OUTPUT matters this
        # step (its last prompt chunk or a decode row): an all-greedy batch
        # keeps the original argmax program
        samp = None
        if any(self._active[u].sampled for u in uids):
            samp = [self._active[u].sampling for u in uids]
        if self.step_observer is None:
            toks = self.engine.put(uids, chunks, sample="greedy", sampling=samp)
        else:
            t0 = time.perf_counter()
            toks = self.engine.put(uids, chunks, sample="greedy", sampling=samp)
            self.step_observer(uids, [c.size for c in chunks], t0,
                               time.perf_counter() - t0, "put")
        n = sum(c.size for c in chunks)
        for uid, tok in zip(uids, np.asarray(toks).reshape(-1)):
            req = self._active[uid]
            if req.prefilling or self._block:
                continue  # mid-prompt chunk: the "next token" is still prompt (diffusion: a prefill emits none)
            self._append_token(req, int(tok))
        return n

    def run(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive to completion. Raises (with partial generations preserved in
        ``results``) if work remains but nothing is runnable — silent drops
        would hide stalled requests."""
        steps = 0
        while self.has_work and steps < max_steps:
            if self.step() == 0:
                stalled = [r.uid for r in self._pending] + list(self._active)
                raise RuntimeError(f"scheduler stalled with unrunnable requests {stalled}: "
                                   "no pending request can be admitted (shrink them, raise "
                                   "the KV pool, or drain active work); partial generations "
                                   "remain in .results")
            steps += 1
        if self.has_work:
            raise RuntimeError(f"max_steps={max_steps} exhausted with work remaining "
                               f"({len(self._pending)} pending, {len(self._active)} active); "
                               "partial generations remain in .results")
        return dict(self._results)
