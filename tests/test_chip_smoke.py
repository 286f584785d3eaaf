"""chip_smoke.py's serve and check functions, run on the CPU in interpret mode.

The smoke script is the proof that the serving path runs on the chip; these
tests keep its logic honest where no chip exists: the no-TPU guard refuses,
the kernel oracle check (b), the reference check (c) and the batch
invariance check (d) pass at DiT-XL/2 widths with the depth cut to 2
layers, and the native-lowering check (a) fails on an interpreted step.
"""
import importlib.util
import os

import numpy as np
import pytest

from repro import configs
from repro.core import diffusion
from repro.launch.steps import make_dit_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def test_no_tpu_guard_refuses_before_serving(capsys):
    with pytest.raises(cs.NoAccelerator):
        cs.require_tpu()
    assert cs.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line off-TPU


def test_kernel_checks_pass_at_published_widths():
    cfg = make_dit_model(configs.get("dit-xl2"))
    out = cs.check_kernels(cfg, cfg.n_tokens)
    assert set(out) == {"act_1152x4608", "act_4608x1152", "diff_kn", "diff_nk",
                        "diff_attn_nk"}
    # the seeded mix reaches every tile class on the linear shapes
    assert all(n > 0 for n in out["diff_kn"]["tiles"])


def test_served_samples_track_the_float32_reference():
    sched = diffusion.cosine_schedule(1000)
    params, cfg = cs.build_model(n_layers=2)
    assert cfg.d_model == 1152 and cfg.n_tokens == 256  # published widths
    requests = cs.make_requests(cfg, (1, 1))  # one warmed bucket-2 dispatch per stream
    served = cs.serve(params, cfg, sched, requests, policy="diff", steps=3, max_batch=2)
    assert served["diff_layers"] == 19 and served["act_layers"] == 0
    refs = cs.reference_samples(params, cfg, sched, requests, steps=3)
    assert 0 < cs.check_samples(served["samples"], refs) <= cs.REL_L2_BOUND
    assert [s.shape[0] for s in served["samples"]] == [1, 1]
    cs.check_batch_invariance(params, cfg, sched, served, requests)  # bucket 1 vs 2
    with pytest.raises(AssertionError, match="interpret"):
        cs.check_native(served["scheduler"])  # interpreted steps are not native
    bad = [np.full_like(s, np.nan) for s in served["samples"]]
    with pytest.raises(AssertionError, match="finite"):
        cs.check_samples(bad, refs)
