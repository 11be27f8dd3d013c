"""Batched serving engine: per-slot continuous batching with batched
prefill and optional dtANS-sparse projection weights.

A fixed pool of batch slots is filled FIFO from a bounded request
queue. Each slot tracks its own cache position (`Engine.pos[s]`;
``-1`` = empty slot), so requests with unequal prompt lengths decode
together — slot s reads and writes KV at exactly ``pos[s]``, never at
another slot's position. Admitting a request runs its whole prompt
through ONE batched forward (`api.prefill`) and scatters the resulting
batch-size-1 cache into the slot (`api.cache_insert_slot`); the other
slots' live cache lines are untouched (the old token-by-token replay
fed zero tokens through every slot and corrupted their KV on each
mid-flight refill). Admission control rejects requests the pool could
never serve correctly — empty prompts and
``prompt_len + max_new_tokens > max_seq`` — at `submit` time, which
makes a slot position walking past ``max_seq`` unreachable.

The pooled cache (`Engine.cache`, f32) is updated in place: the jitted
decode step and slot insert take it as a donated argument, so XLA
writes the step's one K/V row per slot (or the admitted slot's lines)
into the same buffers, and no step copies the whole cache. The cache a
call was given is consumed by it: nothing may hold on to
`Engine.cache` across a step. ``engine.cache_in_place`` counts the
calls whose donated cache was consumed.

Sampling: ``greedy=True`` (default) takes the argmax;
``greedy=False`` samples from the temperature-scaled softmax,
optionally truncated to the ``top_k`` most likely tokens, with a
seeded per-engine generator (two engines with the same ``sample_seed``
reproduce the same stream).

Sparse mode: `compress_lm_head` swaps the output projection for a
SparseLinear (pruned + entropy-coded). The LM head is the single largest
matrix of small LMs (vocab x d) and is matvec-bound at decode — exactly
the paper's target workload. Each pooled decode step stops the jit'd
model at the final norm (`api.decode_hidden`) and contracts the
(slots, 1, d) hidden states against the compressed head in ONE fused
multi-RHS SpMM (`SparseLinear.apply` -> `ops.spmm`): one entropy decode
per step, amortized over every active slot.
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import api
from repro.models.config import ArchConfig
from repro.serving.sparse_linear import SparseLinear


class AdmissionError(ValueError):
    """Request rejected by admission control at `Engine.submit`."""


class QueueFullError(AdmissionError):
    """Request rejected because the FIFO queue is at ``max_queue``."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (len,) int32
    max_new_tokens: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # Observability timestamps (time.perf_counter seconds): submission,
    # first generated token (TTFT = t_first - t_submit), completion
    # (end-to-end latency = t_done - t_submit).
    t_submit: float | None = None
    t_first: float | None = None
    t_done: float | None = None


class Engine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_seq: int = 256, sparse_head: SparseLinear | None = None,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, sample_seed: int = 0,
                 max_queue: int | None = None,
                 metrics: obs.MetricsRegistry | None = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.sparse_head = sparse_head
        self.greedy = greedy
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._sampler = np.random.default_rng(sample_seed)
        self.max_queue = max_queue
        # Metrics land in the process default registry unless the caller
        # isolates them (benchmarks pass a fresh registry per run;
        # `obs.NULL` serves uninstrumented — the overhead baseline).
        self.metrics = metrics if metrics is not None \
            else obs.default_registry()
        m = self.metrics
        self._m_step = m.histogram("engine.step_s")
        self._m_prefill = m.histogram("engine.prefill_s")
        self._m_decode = m.histogram("engine.decode_s")
        self._m_refill = m.histogram("engine.refill_s")
        self._m_occupancy = m.histogram("engine.occupancy")
        self._m_ttft = m.histogram("engine.ttft_s")
        self._m_e2e = m.histogram("engine.e2e_s")
        self._m_tokens = m.counter("engine.tokens_total")
        self._m_steps = m.counter("engine.steps_total")
        self._m_submitted = m.counter("engine.requests_submitted")
        self._m_completed = m.counter("engine.requests_completed")
        self._m_rejected = m.counter("engine.rejections")
        self._m_refills = m.counter("engine.refills_total")
        self._m_d2h = m.counter("engine.d2h_bytes")
        self._m_in_place = m.counter("engine.cache_in_place")
        self._m_queue = m.gauge("engine.queue_depth")
        #: True when the last `run_until_drained` hit ``max_steps`` with
        #: requests still active (only reachable with on_truncate="warn").
        self.truncated = False
        self.queue: list[Request] = []
        self.active: list[Request | None] = [None] * slots
        #: Completed requests in completion order, appended by `step`
        #: and drained by `run_until_drained`.
        self.finished: list[Request] = []
        # Monotonic default rid: the old len(queue) default collided as
        # soon as submits interleaved with steps (queue drains), making
        # drained results ambiguous to correlate.
        self._next_rid = 0
        #: Per-slot cache position: the index the slot's NEXT decode
        #: step writes KV at. -1 = empty slot (backends mask its cache
        #: writes and attention entirely).
        self.pos = np.full(slots, -1, dtype=np.int32)
        self.cache = api.make_decode_cache(cfg, slots, max_seq,
                                           dtype=jnp.float32)
        # A zeroed batch-size-1 cache, scattered into a slot on admission
        # of a 1-token prompt (no prefill runs, but the slot's stale
        # state from its previous occupant must still be cleared).
        self._blank_slot = api.make_decode_cache(cfg, 1, max_seq,
                                                 dtype=jnp.float32)
        # Named functions, so that a device trace shows the modules as
        # jit_engine_decode, jit_engine_decode_hidden, jit_engine_prefill,
        # jit_engine_insert_slot. The cache (argument c) is donated to
        # every jit that returns it: the result reuses its buffers.
        def engine_decode(p, c, t, pos):
            return api.decode_step(p, cfg, c, t, pos)

        # Sparse mode stops the jit'd step at the hidden states; the
        # pooled (slots, 1, d) batch then feeds the compressed head's
        # fused SpMM kernel (one entropy decode per step, amortized
        # over every active slot).
        def engine_decode_hidden(p, c, t, pos):
            return api.decode_hidden(p, cfg, c, t, pos)

        # Batched prefill: the whole prompt in one forward pass. jit
        # retraces once per distinct prompt length (real engines bucket
        # lengths; the pools this repo serves see a handful).
        def engine_prefill(p, b):
            return api.prefill(p, cfg, b, max_seq=max_seq)

        # The slot is traced, so one program serves every slot.
        def engine_insert_slot(c, req, slot):
            return api.cache_insert_slot(cfg, c, req, slot)

        self._decode = jax.jit(engine_decode, donate_argnums=1)
        self._decode_hidden = jax.jit(engine_decode_hidden, donate_argnums=1)
        self._prefill = jax.jit(engine_prefill)
        self._insert_slot = jax.jit(engine_insert_slot, donate_argnums=0)

    def _count_in_place(self, given) -> None:
        """Count a call whose donated cache ``given`` was consumed: its
        buffers went to the result, so the call copied no cache."""
        if all(a.is_deleted() for a in jax.tree.leaves(given)):
            self._m_in_place.add(1)

    # --- sparse head ---------------------------------------------------------
    @classmethod
    def compress_lm_head(cls, cfg, params, sparsity=0.8,
                         **kw) -> SparseLinear:
        """Compress the LM head of ``params`` into a `SparseLinear`.

        Resolves the head weight the same way `models.layers.lm_head`
        does (untied ``head`` or tied ``tok.T``), validates its shape
        against ``cfg`` (a mismatched config would silently compress the
        wrong projection), and hands the weight over in its *source*
        dtype — `SparseLinear.from_dense` preserves float32/float64 end
        to end, so a float64 head serves float64 logits.
        """
        emb = params["embed"]
        w = np.asarray(emb["head"]) if "head" in emb \
            else np.asarray(emb["tok"]).T                # (d, vocab)
        if cfg is not None and w.shape != (cfg.d_model, cfg.vocab):
            raise ValueError(
                f"LM head shape {w.shape} does not match config "
                f"(d_model={cfg.d_model}, vocab={cfg.vocab})")
        return SparseLinear.from_dense(w, sparsity=sparsity, **kw)

    def _head(self, hidden):
        """hidden: (B, 1, d) -> logits (B, 1, vocab) through the
        compressed head's fused SpMM path (`SparseLinear.apply` ->
        `ops.spmm`: decode once, contract all B pooled hidden states).
        The engine's own registry is threaded through so head metrics
        stay isolated with the engine's (`metrics=` contract)."""
        if self.sparse_head is None:
            raise RuntimeError("dense path returns logits directly")
        return self.sparse_head.apply(hidden, metrics=self.metrics)

    # --- scheduler: admission control ----------------------------------------
    def _reject(self, reason: str, msg: str):
        self._m_rejected.add(1)
        self.metrics.counter(f"engine.rejections.{reason}").add(1)
        if reason == "queue_full":
            raise QueueFullError(msg)
        raise AdmissionError(msg)

    def submit(self, prompt, max_new_tokens: int, rid=None) -> Request:
        """Admit a request into the FIFO queue, or raise
        `AdmissionError` / `QueueFullError`.

        Admission rules (each rejection bumps ``engine.rejections`` and
        ``engine.rejections.<reason>``):

        * non-empty prompt — an empty prompt has no last token to feed
          the first decode step (used to crash deep inside `step`);
        * ``max_new_tokens >= 1``;
        * ``prompt_len + max_new_tokens <= max_seq`` — the request's
          final decode position is then ``prompt_len + max_new - 2 <=
          max_seq - 2``, so a slot position can never walk past the
          cache (used to scatter KV out of range);
        * queue depth below ``max_queue`` (when set).
        """
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            self._reject("empty_prompt", "empty prompt rejected: the "
                         "first decode step feeds prompt[-1]")
        if max_new_tokens < 1:
            self._reject("bad_max_new",
                         f"max_new_tokens must be >= 1; "
                         f"got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_seq:
            self._reject(
                "exceeds_max_seq",
                f"prompt_len + max_new_tokens = "
                f"{len(prompt)} + {max_new_tokens} > max_seq="
                f"{self.max_seq}: request would overrun the KV cache")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._reject("queue_full",
                         f"queue at max_queue={self.max_queue}")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        r = Request(rid=rid,
                    prompt=prompt,
                    max_new_tokens=max_new_tokens,
                    t_submit=time.perf_counter())
        self.queue.append(r)
        self._m_submitted.add(1)
        self._m_queue.set(len(self.queue))
        return r

    # --- scheduler: refill + batched prefill ----------------------------------
    def _fill_slots(self):
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                r = self.queue.pop(0)
                self.active[s] = r
                t0 = time.perf_counter()
                with obs.span("engine.prefill", rid=r.rid, slot=s,
                              prompt_len=int(len(r.prompt)),
                              queued_s=None if r.t_submit is None
                              else t0 - r.t_submit):
                    self._prefill_slot(s, r)
                self._m_prefill.observe(time.perf_counter() - t0)
                self._m_refills.add(1)
        self._m_queue.set(len(self.queue))

    def _prefill_slot(self, s: int, r: Request):
        """Admit request ``r`` into slot ``s``: run ``prompt[:-1]``
        through ONE batched `api.prefill` forward and scatter the
        resulting cache into the slot (the last prompt token is fed by
        the first pooled decode step, which produces the first output
        token). Slots other than ``s`` are untouched — no cross-slot
        KV writes, unlike the old per-token replay that fed zero
        tokens through every other slot."""
        L = len(r.prompt)
        if L > 1:
            batch = {"inputs": jnp.asarray(r.prompt[None, :-1])}
            if self.cfg.family == "encdec":
                # No frame frontend flows through `submit`; a zero
                # frame block matches the zero `memory` the pooled
                # decode cache initializes (encode(0) == 0 end to end).
                batch["frontend"] = jnp.zeros(
                    (1, self.cfg.n_frontend_tokens, self.cfg.d_model),
                    dtype=jnp.float32)
            _, req_cache, _ = self._prefill(self.params, batch)
        else:
            # 1-token prompt: nothing to prefill, but the slot's cache
            # lines still hold its previous occupant's state.
            req_cache = self._blank_slot
        with obs.span("engine.insert_slot"):
            given = self.cache
            self.cache = self._insert_slot(given, req_cache, s)
            self._count_in_place(given)
        self.pos[s] = L - 1

    # --- sampling --------------------------------------------------------------
    def _select_token(self, logits_row: np.ndarray) -> int:
        """Next token from one slot's (vocab,) logits: argmax when
        ``greedy``, else seeded temperature/top-k sampling."""
        if self.greedy:
            return int(logits_row.argmax())
        z = logits_row.astype(np.float64) / max(self.temperature, 1e-6)
        if self.top_k and self.top_k < z.size:
            kth = np.partition(z, -self.top_k)[-self.top_k]
            z = np.where(z >= kth, z, -np.inf)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._sampler.choice(z.size, p=p))

    # --- decode ----------------------------------------------------------------
    def step(self) -> int:
        """One pooled decode for all active slots; returns #tokens.

        Each slot decodes at ITS OWN position (`self.pos`, a (slots,)
        vector threaded through `api.decode_step` / `decode_hidden`):
        mixed-length prompts and mid-flight refills stay token-identical
        to running each request alone. Instrumented: step wall time
        splits into refill (admission + batched prefill), pooled decode
        (with the logits' copy to the host) and sampling spans; slot
        occupancy, logits bytes copied, TTFT and end-to-end latency
        land in `self.metrics` (see docs/observability.md for the
        names).
        """
        t_step0 = time.perf_counter()
        with obs.span("engine.step"):
            with obs.span("engine.refill"):
                self._fill_slots()
            t_refill = time.perf_counter() - t_step0
            n_active = sum(r is not None for r in self.active)
            if n_active == 0:
                return 0
            toks = np.zeros((self.slots, 1), dtype=np.int32)
            for s, r in enumerate(self.active):
                if r is not None:
                    toks[s, 0] = (r.out[-1] if r.out else r.prompt[-1])
            # Per-slot positions: empty slots carry -1 and are fully
            # masked inside the model (no KV/SSM writes, no attention).
            pos = jnp.asarray(self.pos)
            t_dec0 = time.perf_counter()
            with obs.span("engine.decode", batch=n_active,
                          sparse=self.sparse_head is not None):
                given = self.cache
                if self.sparse_head is not None:
                    # hidden-state decode, then the compressed LM head:
                    # the pooled (slots, 1, d) hidden states contract
                    # against the entropy-coded head in ONE fused SpMM
                    # (decode amortized over the whole batch) — the
                    # dense in-model head is never consulted in sparse
                    # mode.
                    hidden, self.cache = self._decode_hidden(
                        self.params, given, jnp.asarray(toks), pos)
                    logits = self._head(hidden)
                else:
                    logits, self.cache = self._decode(
                        self.params, given, jnp.asarray(toks), pos)
                self._count_in_place(given)
                nbytes = logits.nbytes     # crosses in the logits' dtype
                self._m_d2h.add(nbytes)
                with obs.span("engine.logits_d2h", bytes=nbytes):
                    logits = np.asarray(logits, dtype=np.float32)
            t_decode = time.perf_counter() - t_dec0
            with obs.span("engine.sample"):
                produced = self._sample(logits)
        dt = time.perf_counter() - t_step0
        self._m_step.observe(dt)
        self._m_refill.observe(t_refill)
        self._m_decode.observe(t_decode)
        self._m_occupancy.observe(n_active / self.slots)
        self._m_tokens.add(produced)
        self._m_steps.add(1)
        return produced

    def _sample(self, logits: np.ndarray) -> int:
        """Pick each active slot's next token from its row of the
        (slots, 1, vocab) host ``logits``, advance the slot and retire
        finished requests; returns #tokens."""
        now = time.perf_counter()
        produced = 0
        for s, r in enumerate(self.active):
            if r is None:
                continue
            nxt = self._select_token(logits[s, 0])
            r.out.append(nxt)
            produced += 1
            self.pos[s] += 1
            if self.pos[s] >= self.max_seq:
                # Unreachable by construction: admission control
                # bounds prompt_len + max_new_tokens <= max_seq.
                raise RuntimeError(
                    f"slot {s} position {int(self.pos[s])} overran "
                    f"max_seq={self.max_seq} — admission control "
                    f"failed")
            if len(r.out) == 1:
                r.t_first = now
                if r.t_submit is not None:
                    self._m_ttft.observe(now - r.t_submit)
            if len(r.out) >= r.max_new_tokens:
                r.done = True
                r.t_done = now
                self.active[s] = None
                self.pos[s] = -1
                self.finished.append(r)
                self._m_completed.add(1)
                if r.t_submit is not None:
                    self._m_e2e.observe(now - r.t_submit)
        return produced

    def run_until_drained(self, max_steps: int = 10000, *,
                          on_truncate: str = "raise") -> list[Request]:
        """Step until queue and slots are empty; returns the completed
        requests in completion order (including any that finished in
        manual `step` calls before this drain and were not yet
        reported).

        Hitting ``max_steps`` with requests still queued or active used
        to return partial results silently — a load test could report a
        truncated run as complete. Now ``on_truncate="raise"`` (default)
        raises RuntimeError; ``"warn"`` emits a UserWarning, sets
        ``self.truncated`` and returns what finished.
        """
        if on_truncate not in ("raise", "warn"):
            raise ValueError(f"on_truncate must be 'raise' or 'warn'; "
                             f"got {on_truncate!r}")
        self.truncated = False
        steps = 0
        while (self.queue or any(self.active)) and steps < max_steps:
            self.step()
            steps += 1
        if self.queue or any(r is not None for r in self.active):
            pending = len(self.queue) + sum(r is not None
                                            for r in self.active)
            msg = (f"run_until_drained hit max_steps={max_steps} with "
                   f"{pending} request(s) still pending — results are "
                   f"truncated")
            self.metrics.counter("engine.drain_truncations").add(1)
            if on_truncate == "raise":
                raise RuntimeError(msg)
            warnings.warn(msg, stacklevel=2)
            self.truncated = True
        finished, self.finished = self.finished, []
        return finished
