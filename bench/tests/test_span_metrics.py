"""The readers of the program's byte and queue spans, on runs built by
hand; each reads nothing from a program that lacks its span."""

import numpy as np
import pytest

from bench import spec
from bench.drivers.serving import Run


def read(name, spans):
    return spec.load_module("metrics", name).read(Run(spans=spans))


def _step(upload=None, d2h=None):
    out = [{"name": "engine.step"}, {"name": "serving.sparse_apply"}]
    if upload is not None:
        out.append({"name": "kernels.upload", "bytes": upload})
    if d2h is not None:
        out.append({"name": "engine.logits_d2h", "bytes": d2h})
    return out


def test_upload_megabytes_per_head_call():
    spans = _step(13_402_112, 3_145_728) * 3
    assert read("head.h2d_mb_per_call", spans) == pytest.approx(13.402112)
    # A pack kept on the device: the span is there, with nothing copied.
    assert read("head.h2d_mb_per_call", _step(0) * 2) == 0.0
    assert read("head.h2d_mb_per_call", _step() * 2) is None
    assert read("head.h2d_mb_per_call", None) is None


def test_logits_megabytes_per_step():
    spans = _step(1, 3_145_728) * 4 + [{"name": "engine.step"}]
    # Five steps, four of which copied logits.
    assert read("engine.d2h_mb_per_step", spans) == pytest.approx(
        4 * 3.145728 / 5)
    assert read("engine.d2h_mb_per_step", _step(1) * 2) is None


def test_queue_wait_p95():
    waits = np.linspace(0.0, 0.099, 100)
    spans = [{"name": "engine.prefill", "prompt_len": 8, "queued_s": w}
             for w in waits] + _step(1, 1)
    assert read("scheduler.queue_wait_ms_p95", spans) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    # Prefill spans without the attribute read nothing.
    assert read("scheduler.queue_wait_ms_p95",
                [{"name": "engine.prefill", "prompt_len": 8}]) is None
