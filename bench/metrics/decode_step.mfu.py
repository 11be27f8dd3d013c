"""The whole engine step's share (%) of the chip's bf16 peak: model FLOPs
of the window's steps over their time.

FLOPs come from the configuration's shapes (`flops_per_token` of its
reference module, linear in the context): every matrix product of the
body, attention over each token's context, and the head as its dense
d_model x vocab product, so the count does not depend on the head's
format.  They are counted for every token decoded in the window and for
the prompt tokens its steps prefilled (the ``engine.prefill`` spans).  The
time is the sum of the ``engine.step`` spans: admission, prefill, the
pooled decode and the head, sampling and bookkeeping."""

from bench import spec


def read(run):
    steps = [s["dur_s"] for s in run.spans or ()
             if s["name"] == "engine.step"]
    if not steps:
        return None
    f0 = run.flops_per_token(0)
    slope = run.flops_per_token(1) - f0
    flops = sum(run.flops_per_token(len(r.prompt) + i)
                for r in run.requests
                for i, t in enumerate(r.token_times) if run.in_window(t))
    for s in run.spans:
        if s["name"] == "engine.prefill":
            n = s["prompt_len"] - 1          # the last token is decoded
            flops += n * f0 + slope * n * (n + 1) / 2
    peak = spec.peaks_of(run.device_kind)["bf16_flops"]
    return 100.0 * flops / (sum(steps) * peak)
