"""Scheduler + per-slot position tests for the serving engine.

The regression pinned here: `Engine.step` used to decode every slot at
``pos.max()`` (wrong KV read/write positions once prompt lengths
differ) and `_fill_slots` replayed prompts token-by-token through the
pooled decode, feeding zero tokens through every *other* slot and
overwriting their live KV at those positions (cross-slot cache
corruption on every mid-flight refill). The conformance bar: pooled
decode over mixed-length prompts with mid-flight refills must be
token-identical to running each request alone.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.models import api
from repro.serving.engine import AdmissionError, Engine, QueueFullError

MIXED_LENS = (1, 3, 7, 12, 5, 2)     # > slots=4 => mid-flight refills
MAX_NEW = 5


def _params_for(arch, vocab=64, seed=0):
    cfg = get_smoke(arch).with_(vocab=vocab)
    return cfg, api.init_params(cfg, jax.random.PRNGKey(seed))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=n) for n in lens]


def _sequential_outputs(cfg, params, prompts, head=None):
    """Ground truth: each request alone in a slots=1 engine (the same
    engine instance, so slot-reset on refill is exercised too)."""
    eng = Engine(cfg, params, slots=1, max_seq=32, sparse_head=head,
                 metrics=obs.MetricsRegistry())
    out = {}
    for p in prompts:
        r = eng.submit(p, MAX_NEW)
        eng.run_until_drained()
        out[r.rid] = list(r.out)
    return out


class TestMixedLengthConformance:
    """slots=4, prompt lengths {1, 3, 7, 12, ...} with mid-flight
    refills == slots=1 sequential, dense and compressed heads, across
    the transformer and hybrid families."""

    @pytest.fixture(scope="class", params=["smollm-135m", "zamba2-7b"])
    def setup(self, request):
        cfg, params = _params_for(request.param)
        head = Engine.compress_lm_head(cfg, params, sparsity=0.6,
                                       value_bits=5, lane_width=32)
        return cfg, params, head

    @pytest.mark.parametrize("use_sparse_head", [False, True],
                             ids=["dense", "compressed"])
    def test_pooled_equals_sequential(self, setup, use_sparse_head):
        cfg, params, head = setup
        head = head if use_sparse_head else None
        prompts = _prompts(cfg, MIXED_LENS)
        want = _sequential_outputs(cfg, params, prompts, head=head)
        eng = Engine(cfg, params, slots=4, max_seq=32, sparse_head=head,
                     metrics=obs.MetricsRegistry())
        reqs = [eng.submit(p, MAX_NEW) for p in prompts]
        done = eng.run_until_drained()
        assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
        for r in reqs:
            assert list(r.out) == want[r.rid], (
                f"rid={r.rid} prompt_len={len(r.prompt)}: pooled decode "
                f"diverged from the solo run — cross-slot KV corruption "
                f"or wrong per-slot position")

    def test_mid_flight_refill_does_not_corrupt_neighbor(self, setup):
        """Explicit shape of the old bug: a long request is mid-decode
        when a refill prefills a new request into the neighboring slot;
        the long request's tokens must be unchanged vs running alone."""
        cfg, params, _ = setup
        prompts = _prompts(cfg, (9,), seed=3)
        want = _sequential_outputs(cfg, params, prompts)
        eng = Engine(cfg, params, slots=2, max_seq=32,
                     metrics=obs.MetricsRegistry())
        long_req = eng.submit(prompts[0], 8)
        eng.step()
        eng.step()          # long request is now mid-flight
        rng = np.random.default_rng(4)
        eng.submit(rng.integers(0, cfg.vocab, size=4), 2)
        eng.run_until_drained()
        assert list(long_req.out)[:MAX_NEW] == want[long_req.rid]


class TestAdmissionControl:
    @pytest.fixture(scope="class")
    def setup(self):
        return _params_for("smollm-135m", vocab=32, seed=1)

    def test_empty_prompt_rejected(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, slots=2, max_seq=16,
                     metrics=obs.MetricsRegistry())
        with pytest.raises(AdmissionError, match="empty prompt"):
            eng.submit(np.array([], dtype=np.int32), 4)
        # a rejected request never enters the queue or the counters
        assert eng.queue == []
        assert eng.metrics.counter("engine.rejections").value == 1
        assert eng.metrics.counter(
            "engine.rejections.empty_prompt").value == 1
        assert eng.metrics.counter(
            "engine.requests_submitted").value == 0

    def test_zero_max_new_tokens_rejected(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, slots=2, max_seq=16,
                     metrics=obs.MetricsRegistry())
        with pytest.raises(AdmissionError, match="max_new_tokens"):
            eng.submit(np.array([1, 2]), 0)

    def test_over_max_seq_rejected_at_boundary(self, setup):
        """prompt_len + max_new == max_seq is admitted and drains;
        one past is rejected at submit (not a later crash or a silent
        out-of-range KV scatter)."""
        cfg, params = setup
        eng = Engine(cfg, params, slots=1, max_seq=12,
                     metrics=obs.MetricsRegistry())
        with pytest.raises(AdmissionError, match="max_seq"):
            eng.submit(np.arange(9) % cfg.vocab, 4)      # 13 > 12
        r = eng.submit(np.arange(8) % cfg.vocab, 4)      # 12 == 12
        # prove the boundary: positions never reach max_seq mid-run
        max_pos = -1
        while eng.queue or any(s is not None for s in eng.active):
            eng.step()
            max_pos = max(max_pos, int(eng.pos.max()))
        assert r.done and len(r.out) == 4
        # last KV write lands at max_seq - 2 (the post-increment value
        # max_seq - 1 is reset to -1 when the request completes)
        assert max_pos == eng.max_seq - 2

    def test_unbounded_position_walk_is_unreachable(self, setup):
        """The old engine accepted any request and let `pos` walk past
        `max_seq` (out-of-range KV scatter). Every admitted request now
        has prompt_len + max_new <= max_seq, so the defensive overrun
        check in `step` can never fire."""
        cfg, params = setup
        eng = Engine(cfg, params, slots=2, max_seq=10,
                     metrics=obs.MetricsRegistry())
        rng = np.random.default_rng(5)
        for _ in range(4):
            eng.submit(rng.integers(0, cfg.vocab, size=5), 5)
        eng.run_until_drained()      # RuntimeError if a slot overran
        assert int(eng.pos.max()) == -1

    def test_queue_limit_fifo(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, slots=1, max_seq=16, max_queue=2,
                     metrics=obs.MetricsRegistry())
        rng = np.random.default_rng(6)
        r1 = eng.submit(rng.integers(0, cfg.vocab, size=2), 1)
        r2 = eng.submit(rng.integers(0, cfg.vocab, size=2), 1)
        with pytest.raises(QueueFullError, match="max_queue"):
            eng.submit(rng.integers(0, cfg.vocab, size=2), 1)
        assert eng.metrics.counter(
            "engine.rejections.queue_full").value == 1
        done = eng.run_until_drained()
        # FIFO: admitted requests complete in submission order
        assert [r.rid for r in done] == [r1.rid, r2.rid]
        # queue drained => new submits are admitted again
        eng.submit(rng.integers(0, cfg.vocab, size=2), 1)
        eng.run_until_drained()

    def test_scheduler_metrics(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, slots=2, max_seq=16,
                     metrics=obs.MetricsRegistry())
        rng = np.random.default_rng(7)
        for _ in range(3):
            eng.submit(rng.integers(0, cfg.vocab, size=3), 2)
        eng.run_until_drained()
        snap = eng.metrics.snapshot()
        assert snap["counters"]["engine.refills_total"] == 3
        assert snap["counters"]["engine.rejections"] == 0


class TestSampling:
    """greedy=False wires temperature/top-k sampling to a seeded
    per-engine generator (the `greedy` flag used to be stored and never
    read — argmax was hardcoded)."""

    @pytest.fixture(scope="class")
    def setup(self):
        return _params_for("smollm-135m", vocab=48, seed=2)

    def _drain_one(self, cfg, params, **kw):
        eng = Engine(cfg, params, slots=2, max_seq=32,
                     metrics=obs.MetricsRegistry(), **kw)
        r = eng.submit(np.array([1, 2, 3]), 6)
        eng.run_until_drained()
        return list(r.out)

    def test_seeded_sampling_reproduces(self, setup):
        cfg, params = setup
        a = self._drain_one(cfg, params, greedy=False, temperature=0.8,
                            top_k=5, sample_seed=7)
        b = self._drain_one(cfg, params, greedy=False, temperature=0.8,
                            top_k=5, sample_seed=7)
        c = self._drain_one(cfg, params, greedy=False, temperature=0.8,
                            top_k=5, sample_seed=8)
        assert a == b
        assert a != c
        assert all(0 <= t < cfg.vocab for t in a)

    def test_top_k_one_is_greedy(self, setup):
        """top_k=1 truncates the distribution to the argmax — sampling
        must then reproduce the greedy stream exactly, any seed."""
        cfg, params = setup
        greedy = self._drain_one(cfg, params, greedy=True)
        sampled = self._drain_one(cfg, params, greedy=False,
                                  temperature=1.3, top_k=1,
                                  sample_seed=99)
        assert sampled == greedy

    def test_sampling_pooled_with_mixed_lengths(self, setup):
        """The sampling path composes with per-slot positions: a pooled
        mixed-length drain under greedy=False completes and stays
        reproducible under the same seed."""
        cfg, params = setup
        outs = []
        for _ in range(2):
            eng = Engine(cfg, params, slots=3, max_seq=32, greedy=False,
                         temperature=0.9, top_k=8, sample_seed=11,
                         metrics=obs.MetricsRegistry())
            reqs = [eng.submit(p, 4)
                    for p in _prompts(cfg, (2, 6, 9, 4), seed=8)]
            eng.run_until_drained()
            outs.append([list(r.out) for r in reqs])
        assert outs[0] == outs[1]


class TestSparseLinearMetricsIsolation:
    """`SparseLinear.apply` used to record into the process default
    registry unconditionally, ignoring the `metrics=` isolation the
    Engine offers — dense-vs-compressed benchmark runs
    cross-contaminated each other's `serving.*` instruments."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg, params = _params_for("smollm-135m", vocab=48, seed=3)
        head = Engine.compress_lm_head(cfg, params, sparsity=0.6,
                                       value_bits=5, lane_width=32)
        return cfg, params, head

    def test_apply_threads_registry(self, setup):
        _, _, head = setup
        reg_a, reg_b = obs.MetricsRegistry(), obs.MetricsRegistry()
        x = np.ones((2, head.d_in), dtype=np.float32)
        head.apply(x, metrics=reg_a)
        head.apply(x, metrics=reg_b)
        head.apply(x, metrics=reg_b)
        assert reg_a.counter("serving.sparse_apply_calls").value == 1
        assert reg_b.counter("serving.sparse_apply_calls").value == 2
        assert reg_b.histogram("serving.apply_batch").count == 2

    def test_engine_isolates_head_metrics(self, setup):
        """Two engines sharing ONE compressed head, each with its own
        registry: every head record lands in its engine's registry and
        the process default sees none of them."""
        cfg, params, head = setup
        default_before = obs.default_registry().counter(
            "serving.sparse_apply_calls").value
        regs = [obs.MetricsRegistry(), obs.MetricsRegistry()]
        rng = np.random.default_rng(9)
        for reg in regs:
            eng = Engine(cfg, params, slots=2, max_seq=16,
                         sparse_head=head, metrics=reg)
            eng.submit(rng.integers(0, cfg.vocab, size=3), 2)
            eng.run_until_drained()
        for reg in regs:
            assert reg.counter("serving.sparse_apply_calls").value > 0
        assert obs.default_registry().counter(
            "serving.sparse_apply_calls").value == default_before

    def test_default_registry_still_default(self, setup):
        """Un-threaded callers keep the old behavior: records land in
        the process default registry."""
        _, _, head = setup
        before = obs.default_registry().counter(
            "serving.sparse_apply_calls").value
        head.apply(np.ones((1, head.d_in), dtype=np.float32))
        assert obs.default_registry().counter(
            "serving.sparse_apply_calls").value == before + 1


class TestEncdecPerSlot:
    """The encdec family threads the same per-slot position vector
    (cross-attention reads the per-slot memory; self-attention KV
    scatters at pos[s])."""

    def test_mixed_length_drain(self):
        cfg, params = _params_for("seamless-m4t-large-v2", vocab=48,
                                  seed=4)
        prompts = _prompts(cfg, (2, 5, 3), seed=10)
        want = _sequential_outputs(cfg, params, prompts)
        eng = Engine(cfg, params, slots=2, max_seq=32,
                     metrics=obs.MetricsRegistry())
        reqs = [eng.submit(p, MAX_NEW) for p in prompts]
        eng.run_until_drained()
        for r in reqs:
            assert list(r.out) == want[r.rid]


class TestSpans:
    """With tracing on, each step records where its host time goes:
    admission (prefill, cache insert), the pooled decode with the
    head's upload and the logits' copy to the host, and sampling."""

    PARENT = {"engine.refill": "engine.step",
              "engine.prefill": "engine.refill",
              "engine.insert_slot": "engine.prefill",
              "engine.decode": "engine.step",
              "serving.sparse_apply": "engine.decode",
              "kernels.upload": "serving.sparse_apply",
              "engine.logits_d2h": "engine.decode",
              "engine.sample": "engine.step"}

    @pytest.fixture(scope="class", params=["dense", "compressed"])
    def traced(self, request, tmp_path_factory):
        cfg, params = _params_for("smollm-135m", vocab=64, seed=5)
        head = None
        if request.param == "compressed":
            head = Engine.compress_lm_head(cfg, params, sparsity=0.6,
                                           value_bits=5, lane_width=32)
        eng = Engine(cfg, params, slots=3, max_seq=32, sparse_head=head,
                     metrics=obs.MetricsRegistry())
        h2d = obs.default_registry().counter("kernels.h2d_bytes")
        h2d_before = h2d.value
        path = tmp_path_factory.mktemp("spans") / "trace.jsonl"
        obs.configure_trace(path)
        try:
            for p in _prompts(cfg, (1, 4, 6, 3), seed=11):
                eng.submit(p, 3)
            eng.run_until_drained()
        finally:
            obs.configure_trace(None)
        with open(path) as f:
            spans = [r for r in map(json.loads, f) if r["type"] == "span"]
        return eng, spans, h2d.value - h2d_before

    def test_spans_nest_under_their_parents(self, traced):
        eng, spans, _ = traced
        by_id = {s["id"]: s for s in spans}
        names = {s["name"] for s in spans}
        want = set(self.PARENT) | {"engine.step"}
        if eng.sparse_head is None:
            want -= {"serving.sparse_apply", "kernels.upload"}
        assert names == want
        for s in spans:
            if s["name"] in self.PARENT:
                assert by_id[s["parent"]]["name"] == self.PARENT[s["name"]]
        steps = [s for s in spans if s["name"] == "engine.step"]
        for kind in ("engine.decode", "engine.sample",
                     "engine.logits_d2h"):
            assert sum(s["name"] == kind for s in spans) == len(steps)
        prefills = [s for s in spans if s["name"] == "engine.prefill"]
        assert len(prefills) == 4
        assert all(s["queued_s"] >= 0 for s in prefills)
        assert sorted(s["prompt_len"] for s in prefills) == [1, 3, 4, 6]

    @pytest.mark.parametrize("traced", ["compressed"], indirect=True)
    def test_upload_bytes_are_the_packs_host_bytes(self, traced):
        eng, spans, h2d = traced
        pm = eng.sparse_head.packed
        host = sum(a.nbytes for a in (
            pm.stream, pm.stream_base, pm.esc, pm.esc_base, pm.nsegs,
            pm.nnz, pm.tab_symbol, pm.tab_meta))
        up = [s["bytes"] for s in spans if s["name"] == "kernels.upload"]
        assert up and all(b == host for b in up)
        assert h2d == sum(up)

    def test_logits_bytes_are_slots_by_vocab(self, traced):
        eng, spans, _ = traced
        toks = jnp.zeros((eng.slots, 1), jnp.int32)
        pos = jnp.asarray(eng.pos)
        if eng.sparse_head is None:
            logits = jax.eval_shape(eng._decode, eng.params, eng.cache,
                                    toks, pos)[0]
        else:
            hidden = jax.eval_shape(eng._decode_hidden, eng.params,
                                    eng.cache, toks, pos)[0]
            logits = jax.eval_shape(eng._head, hidden)
        want = eng.slots * eng.cfg.vocab * logits.dtype.itemsize
        d2h = [s["bytes"] for s in spans if s["name"] == "engine.logits_d2h"]
        assert d2h and all(b == want for b in d2h)
        assert eng.metrics.counter("engine.d2h_bytes").value == sum(d2h)

    def test_jitted_steps_have_stable_names(self):
        """A device trace names each module after its function, so the
        engine's jits are named functions, not lambdas."""
        cfg, params = _params_for("smollm-135m", vocab=64, seed=5)
        eng = Engine(cfg, params, slots=2, max_seq=16,
                     metrics=obs.MetricsRegistry())
        step = (eng.params, eng.cache, jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(eng.pos))
        prompt = {"inputs": jnp.zeros((1, 3), jnp.int32)}
        insert = (eng.cache, eng._blank_slot, 0)
        for fn, args, name in (
                (eng._decode, step, "jit_engine_decode"),
                (eng._decode_hidden, step, "jit_engine_decode_hidden"),
                (eng._prefill, (eng.params, prompt), "jit_engine_prefill"),
                (eng._insert_slot, insert, "jit_engine_insert_slot")):
            assert f"module @{name} " in fn.lower(*args).as_text()

    @pytest.mark.parametrize("arch", ["smollm-135m", "zamba2-7b"])
    def test_jitted_steps_donate_the_cache(self, arch):
        """Every jit that returns the pooled cache takes it donated, and
        nothing else: its lowered module aliases each cache leaf to an
        output buffer."""
        cfg, params = _params_for(arch, vocab=64, seed=5)
        eng = Engine(cfg, params, slots=2, max_seq=16,
                     metrics=obs.MetricsRegistry())
        n_cache = len(jax.tree.leaves(eng.cache))
        step = (eng.params, eng.cache, jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(eng.pos))
        for fn, args, at in (
                (eng._decode, step, 1),
                (eng._decode_hidden, step, 1),
                (eng._insert_slot, (eng.cache, eng._blank_slot, 0), 0)):
            lowered = fn.lower(*args)
            donated = [jax.tree.leaves(jax.tree.map(lambda a: a.donated, x))
                       for x in lowered.args_info[0]]
            for i, flags in enumerate(donated):
                assert flags == [i == at] * len(flags)
            assert lowered.as_text().count("tf.aliasing_output") == n_cache

    def test_cache_in_place_counts_every_step_and_admission(self):
        """The CPU backend honours donation, so every decode step and
        every admission consumes the cache it was given."""
        cfg, params = _params_for("smollm-135m", vocab=64, seed=5)
        m = obs.MetricsRegistry()
        eng = Engine(cfg, params, slots=2, max_seq=32, metrics=m)
        for p in _prompts(cfg, (1, 4, 6)):
            eng.submit(p, 3)
        eng.run_until_drained()
        steps = m.counter("engine.steps_total").value
        admissions = m.counter("engine.refills_total").value
        assert (steps, admissions) == (6, 3)
        assert m.counter("engine.cache_in_place").value == steps + admissions
