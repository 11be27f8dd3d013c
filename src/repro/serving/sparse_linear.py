"""SparseLinear: a dense projection replaced by a pruned, entropy-coded
weight matrix decoded on the fly (the paper's LLM-inference motivation,
Section I, made concrete).

Pipeline: dense W (d_in, d_out) -> magnitude prune -> codebook-quantize
surviving values (8-bit centroids make the value distribution low-entropy,
which is what dtANS compresses; raw float32 mantissas would all escape) ->
CSR-dtANS encode of W^T (so y = W^T-rows . x = SpMVM per output neuron).

`apply` contracts a batch of activations against the decoded matrix
through the fused multi-RHS Pallas kernel (`ops.spmm`): one entropy
decode per call, amortized over every request in the batch — the same
kernel machinery as `kernels/dtans_spmv`, batched.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.csr_dtans import CSRdtANS, encode_matrix
from repro.kernels import ops
from repro.kernels.pack import PackedMatrix, pack_matrix
from repro.sparse.formats import CSR, best_baseline_nbytes
from repro.sparse.prune import codebook_quantize, magnitude_prune


@dataclasses.dataclass
class SparseLinear:
    mat: CSRdtANS            # encodes W^T: (d_out rows, d_in cols)
    packed: PackedMatrix
    d_in: int
    d_out: int
    dense_bytes: int
    baseline_bytes: int      # best of CSR/COO/SELL on the pruned matrix
    decision: object = None  # autotune Decision when built with auto=True
    mesh: object = None      # jax mesh the layer serves from (or None)
    n_shards: int = 1        # row shards of the weight (1 = one chip)
    plan: object = None      # sparse.shard.ShardPlan when n_shards > 1

    @classmethod
    def from_dense(cls, w: np.ndarray, sparsity: float = 0.8,
                   value_bits: int = 8, lane_width: int = 128,
                   shared_table: bool = True, auto: bool = False,
                   autotune_budget: int = 0,
                   autotune_batch: int = 1,
                   autotune_cache=None,
                   autotune_measure: bool = False,
                   autotune_machine=None,
                   mesh=None, n_shards: int | None = None
                   ) -> "SparseLinear":
        """Compress a dense projection for decode-on-the-fly serving.

        The source dtype is preserved end-to-end: a float64 projection
        prunes, quantizes, encodes and decodes in float64 (non-float
        inputs fall back to float32 — the format codes float bit
        patterns).

        With ``auto=True`` the ``lane_width`` / ``shared_table`` knobs are
        ignored and chosen per matrix by `repro.autotune` (fingerprint the
        pruned weight, pick the modeled-fastest entropy-coded
        configuration among every ``decodes=True`` family in
        `repro.sparse.registry` — plain CSR-dtANS, group-aligned
        RGCSR-dtANS, block-aligned BCSR-dtANS, ...; every such family
        runs the same decode kernels, so serving is indifferent, and the
        winning spec's `FormatSpec.encode` builds the artifact — no
        per-format branch here; decisions persist in the autotune cache,
        so repeated serving runs skip the search). ``autotune_budget`` >
        0 additionally encodes the
        top candidates to refine estimated sizes into exact ones;
        ``autotune_measure=True`` further wall-clock times those
        candidates' decode kernels and picks the measured-fastest
        (`repro.autotune.measure`); ``autotune_batch`` prices the
        selection for a ``B``-RHS serving batch (decode amortizes over
        the batch — the knob to set to the expected pool size);
        ``autotune_machine`` substitutes a
        calibrated `MachineModel` (e.g. ``load_profile(...)``) for the
        default v5e constants; ``autotune_cache`` overrides the default
        persistent cache (pass ``repro.autotune.DecisionCache(path=None)``
        for memory-only).

        ``mesh`` builds the layer for multi-chip serving: the pruned
        weight is row-partitioned into ``model_axis_size(mesh)`` shards
        along the winning format's decode-slice boundaries
        (`FormatSpec.shard`) and `apply` routes through the shard_map +
        psum path (`repro.kernels.shard_ops`) — every device decodes
        only its shard's bitstream. ``n_shards`` pins the shard count
        explicitly (usable without a mesh: the sequential sharded path,
        mostly for tests). The selection, when ``auto=True``, is priced
        at the same shard count it will serve on.
        """
        from repro.sparse.registry import get_format
        d_in, d_out = w.shape
        w_arr = np.asarray(w)
        if w_arr.dtype not in (np.float32, np.float64):
            w_arr = w_arr.astype(np.float32)
        pruned = magnitude_prune(w_arr.T, sparsity)
        pruned = codebook_quantize(pruned, bits=value_bits)
        if n_shards is not None:
            k = int(n_shards)
        elif mesh is not None:
            from repro.launch.mesh import model_axis_size
            k = model_axis_size(mesh)
        else:
            k = 1
        decision = None
        if auto:
            from repro.autotune import V5E, choose_dtans_config
            decision = choose_dtans_config(
                pruned, warm=True, budget=autotune_budget,
                batch=autotune_batch, n_shards=k,
                # The timing harness is single-device; sharded builds
                # select on the modeled sharded cost instead.
                measure=autotune_measure if k == 1 else False,
                machine=autotune_machine
                if autotune_machine is not None else V5E,
                cache=autotune_cache)
            spec = get_format(decision.fmt)
            knobs = decision.knobs_dict()
            mat = spec.encode(pruned, **knobs)
        else:
            spec = get_format("dtans")
            knobs = {"lane_width": lane_width,
                     "shared_table": shared_table}
            mat = encode_matrix(pruned, lane_width=lane_width,
                                shared_table=shared_table)
        plan = spec.shard(pruned, k, **knobs) if k > 1 else None
        _, bb = best_baseline_nbytes(pruned)
        return cls(mat=mat, packed=pack_matrix(mat), d_in=d_in,
                   d_out=d_out, dense_bytes=w.size * w.dtype.itemsize,
                   baseline_bytes=bb, decision=decision, mesh=mesh,
                   n_shards=k, plan=plan)

    @property
    def compressed_bytes(self) -> int:
        return self.mat.nbytes

    @property
    def compression_vs_dense(self) -> float:
        return self.dense_bytes / self.mat.nbytes

    @property
    def compression_vs_best_sparse(self) -> float:
        return self.baseline_bytes / self.mat.nbytes

    def apply(self, x, *, bn=None, pipeline: bool = False,
              metrics: obs.MetricsRegistry | None = None):
        """x: (..., d_in) -> (..., d_out).

        Every batch size routes through the fused Pallas SpMM kernel
        (`ops.spmm`): the matrix decodes ONCE per call and contracts
        against all B flattened rows of ``x`` in-kernel — the multi-RHS
        generalization of the paper's SpMVM (B == 1 runs the
        single-vector kernel and is bit-identical to `ops.spmv`).
        Accumulation happens in the packed matrix's dtype
        (`ops.out_dtype`) — a float64 weight contracts in float64.

        Large batches route through the grid-blocked path
        automatically: `ops.spmm` column-tiles the RHS when the
        flattened batch's x/y working set overflows the kernel VMEM
        budget (`repro.kernels.tiling.choose_bn`), so a training-shaped
        ``B = batch * seq`` pool never needs x/y resident whole — and
        the blocked result is bit-identical to the unblocked kernel.
        ``bn`` pins the column-tile width explicitly; ``pipeline``
        double-buffers the entropy decode behind the contraction.

        ``metrics``: registry the ``serving.*`` instruments land in
        (the process default when omitted). Callers that isolate their
        instrumentation — `Engine(metrics=...)` threads its own
        registry through — keep dense-vs-compressed benchmark runs from
        cross-contaminating each other's ``serving.*`` numbers.
        """
        dt = ops.out_dtype(self.packed)
        lead = x.shape[:-1]
        xb = jnp.asarray(x, dtype=dt).reshape(-1, self.d_in)
        reg = metrics if metrics is not None else obs.default_registry()
        reg.counter("serving.sparse_apply_calls").add(1)
        reg.histogram("serving.apply_batch").observe(xb.shape[0])
        with obs.span("serving.sparse_apply", batch=int(xb.shape[0]),
                      d_in=self.d_in, d_out=self.d_out,
                      n_shards=int(self.n_shards)):
            if self.plan is not None:
                from repro.kernels import shard_ops
                y = shard_ops.shard_spmm(self.plan, xb.T,
                                         mesh=self.mesh,
                                         bn=bn, pipeline=pipeline)
            else:
                y = ops.spmm(self.packed, xb.T, bn=bn,
                             pipeline=pipeline)  # (d_out, B)
        return y.T.reshape(*lead, self.d_out).astype(x.dtype)

    def apply_dense_reference(self, x):
        """Oracle: decode to dense and matmul at HIGHEST precision
        (tests, chip smoke). Contracts in the matrix dtype, like
        `apply`."""
        from repro.core.csr_dtans import decode_matrix
        w = decode_matrix(self.mat).to_dense()   # (d_out, d_in)
        return jnp.matmul(jnp.asarray(x, dtype=w.dtype), jnp.asarray(w).T,
                          precision=jax.lax.Precision.HIGHEST
                          ).astype(x.dtype)
