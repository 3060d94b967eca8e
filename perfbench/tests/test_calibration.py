"""The ``dms-cr8`` configuration's ``logit_bias`` marks about 1 - 1/8 of
the (layer, KV head, token) decisions for eviction, at the published
widths, on the weights of the seed it was calibrated with (float32
reference, one layer at a time: about 1.5 GB on the CPU)."""
import os

import numpy as np
import pytest

from perfbench import calibrate, spec

CFG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                   "qwen-r1-1.5b.dms-cr8.json")


def test_calibrated_bias_evicts_seven_eighths():
    cfg = spec.load_json(CFG)
    seed = cfg["dms"]["calibration"]["seed"]
    tokens = np.random.default_rng(seed).integers(
        3, cfg["hf"]["vocab_size"], size=(512,)).astype(np.int32)
    got = calibrate.eviction_share(cfg, seed, tokens)
    assert got["share"] == pytest.approx(1 - 1 / cfg["policy"]["cr"],
                                         abs=0.02)
