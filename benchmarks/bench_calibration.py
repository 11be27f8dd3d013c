"""MachineModel calibration section: fit the cost-model constants to
measured kernel times and report how far the hand-tuned defaults were.

Rows:
  calib/point/<matrix>_<config> — measured vs modeled-before/after
    seconds for every sweep measurement;
  calib/err_before, calib/err_after — mean |modeled - measured| /
    measured across the sweep under the default V5E constants vs the
    fitted ones (the ISSUE's acceptance number);
  calib/constants — the fitted MachineModel, also persisted as a named
    machine profile when ``profile_json`` is given (the CI timing-smoke
    leg uploads that file next to the fig9 smoke JSON).

On CPU hosts the kernels run in Pallas interpret mode, so the fitted
constants describe the *harness*, not a TPU — the point the section
demonstrates is the calibration loop itself: measured times in, a
MachineModel with a distinct cache signature and a smaller
modeled-vs-measured error out.
"""

from __future__ import annotations

from repro.autotune import calibrate, save_profile
from repro.backend import interpret_mode


def run(small: bool = False, profile_json: str | None = None,
        repeats: int = 2):
    res = calibrate(small=small, repeats=repeats)
    rows = []
    for p in res.points:
        rows.append((f"calib/point/{p.matrix}_{p.config_name}",
                     p.measured * 1e6,
                     f"modeled_before={p.modeled_before:.3e};"
                     f"modeled_after={p.modeled_after:.3e};"
                     f"measured={p.measured:.3e}"))
    rows.append(("calib/err_before", 0.0, f"{res.err_before:.4f}"))
    rows.append(("calib/err_after", 0.0, f"{res.err_after:.4f}"))
    m = res.model
    rows.append(("calib/constants", 0.0,
                 f"name={m.name};hbm_bw={m.hbm_bw:.4g};"
                 f"cache_bw={m.cache_bw:.4g};"
                 f"spmv_ops_per_elem={m.spmv_ops_per_elem:.4g};"
                 f"row_seq_penalty={m.row_seq_penalty:.4g};"
                 f"decode_ops_per_nnz={m.decode_ops_per_nnz:.4g}"))
    if profile_json:
        path = save_profile(m, meta={"err_before": res.err_before,
                                     "err_after": res.err_after,
                                     "points": len(res.points),
                                     "interpret": interpret_mode()},
                            path=profile_json)
        rows.append(("calib/profile", 0.0, f"saved={path}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
