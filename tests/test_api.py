"""High-level API (the OpenMP layer): plan / train / serve one-call shims
over the Cluster façade."""

import jax
import numpy as np
import pytest

from repro import api
from repro.core import compat


def test_plan_regions():
    mesh = compat.abstract_mesh((2, 2), ("data", "model"))
    p = api.plan("qwen3-14b", mesh)
    ffn = next(v for k, v in p.items() if k.endswith("w_gate"))
    assert ffn["region"] == "INTERLEAVED"
    norm = next(v for k, v in p.items() if "ln_f" in k)
    assert norm["region"] == "REPLICATED"
    assert len(p) > 10


@pytest.mark.slow
def test_train_and_serve_one_call(tmp_path):
    report = api.train("xlstm-125m", num_steps=4, batch=2, seq=16,
                       checkpoint_dir=str(tmp_path))
    assert report["final_step"] == 4
    out = api.serve("xlstm-125m", report["params"], batch=2, max_seq=16,
                    max_new=4)
    assert out["tokens"].shape == (2, 5)
    assert out["stats"]["emitted_per_slot"] == [4, 4]
    assert out["stats"]["stall"]["host_syncs"] == 4   # chunk 1: one a token


@pytest.mark.slow
def test_train_steps_alias_deprecated(tmp_path):
    """The old steps_ keyword still works (one release) but warns."""
    with pytest.deprecated_call():
        report = api.train("xlstm-125m", steps_=2, batch=2, seq=16,
                           checkpoint_dir=str(tmp_path))
    assert report["final_step"] == 2
