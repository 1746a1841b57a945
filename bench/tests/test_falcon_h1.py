"""Falcon-H1 in the benchmark, at smoke widths on the CPU, in a checkout
whose smoke configuration and cells were added as files and entries only:
the plain reference against the served path (prefill and decode through
the paged session, with a shared prefix that must not be skipped), the
check's verdict on a served path whose recurrent state never updates and
on the fp8 control, the counts against a hand count of one published
layer, and the configuration against the program's registered widths.

The smoke configuration keeps the published multipliers where the
reference's INIT does not undo them, and scales the others by the ratio of
published to smoke widths, so that every branch moves the residual stream
by as much as it does at the published widths."""

from __future__ import annotations

import importlib.util
import json
import math
import shutil

import jax
import numpy as np
import pytest

import run
from conftest import BENCH, ROOT, SMOKE_TRAFFIC
from harness import serve, spec, weights

SEED = 2 ** 31 + 4243
PUBLISHED = json.loads(
    (BENCH / "configs" / "falcon-h1-34b-d6.json").read_text())


def _smoke_config() -> dict:
    p = PUBLISHED
    d, di, hhd, ff = 64, 64, 4 * 16, 128
    up = lambda key, wide, narrow: p[key] * math.sqrt(wide / narrow)
    mult = {
        "lm_head_multiplier": up("lm_head_multiplier", 5120, d),
        "key_multiplier": up("key_multiplier", 5120, d),
        "attention_out_multiplier": up("attention_out_multiplier", 20 * 128,
                                       hhd),
        "ssm_in_multiplier": up("ssm_in_multiplier", 5120, d),
        "ssm_out_multiplier": up("ssm_out_multiplier", 4096, di),
    }
    names = {"lm_head_multiplier": "lm_head_mult",
             "key_multiplier": "key_mult",
             "attention_out_multiplier": "attn_out_mult",
             "ssm_in_multiplier": "ssm_in_mult",
             "ssm_out_multiplier": "ssm_out_mult"}
    cfg = dict(p, hidden_size=d, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=ff,
               vocab_size=256, num_hidden_layers=2, mamba_d_ssm=di,
               mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
               mamba_n_groups=2, **mult)
    cfg["bench"] = dict(
        p["bench"],
        program={"arch": "falcon-h1-34b-smoke",
                 "set": {"n_kv_heads": 2,
                         **{names[k]: v for k, v in mult.items()}}},
        serving={"slots": 4, "max_seq": 128, "max_prompt": 128,
                 "page_size": 16, "chunk": 4, "n_pages": None})
    return cfg


# at these widths on the CPU, over five seeds, the program reads at most
# 0.021 and the fp8 control at least 0.12; a state that never updates
# reads 0.04 to 0.79 (0.79 on SEED)
SMOKE_CELL = {"correct": {"max_logit_gap": 0.05, "sample": 4,
                          "min_checked_tokens": 10}}
BATCH = "falcon-smoke.batch-smoke"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("falcon-checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = root / "bench"
    (bench / "configs" / "falcon-smoke.json").write_text(
        json.dumps(_smoke_config()))
    (bench / "traffic" / "batch-smoke.json").write_text(
        json.dumps(SMOKE_TRAFFIC["batch-smoke"]))
    (bench / "cells" / f"{BATCH}.json").write_text(json.dumps(SMOKE_CELL))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "falcon-smoke", "source": "test",
                         "file": "bench/configs/falcon-smoke.json",
                         "reduced": [], "why": "smoke"})
    b["workloads"].append({"name": BATCH, "config": "falcon-smoke",
                           "traffic": "batch-smoke", "chips": 1,
                           "why": "smoke"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "falcon-h1-34b-d6.batch-decode" in m.get("workloads", ()):
            m["workloads"].append(BATCH)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="module")
def cell(root):
    return spec.load_cell(BATCH, root)


@pytest.fixture(scope="module")
def ref(cell):
    return spec.reference_module(cell)


def _draw(cell, ref):
    return (weights.layer_drawer(ref.layer_shapes(cell.config), ref.INIT),
            weights.layer_drawer(ref.top_shapes(cell.config), ref.INIT))


def test_reference_draws_the_programs_weights(cell, ref):
    server = serve.Server(cell, SEED, ref.INIT)
    flat = {"/".join(str(getattr(k, "key", k)) for k in p): v
            for p, v in jax.tree_util.tree_flatten_with_path(
                server.params)[0]}
    draw_layer, draw_top = _draw(cell, ref)
    for layer in range(cell.config["num_hidden_layers"]):
        for path, v in draw_layer(SEED, layer).items():
            assert np.array_equal(np.asarray(flat[path][layer]),
                                  np.asarray(v)), path
    for path, v in draw_top(SEED, 0).items():
        assert np.array_equal(np.asarray(flat[path]), np.asarray(v)), path
    assert set(flat) == set(ref.layer_shapes(cell.config)) | set(
        ref.top_shapes(cell.config))
    server.close()


def test_paged_session_matches_reference_over_a_shared_prefix(cell, ref):
    """Prefill and decode through the paged session, as the harness opens
    it (prefix cache asked for), against the reference's full forward
    pass: a two-page prompt, then it again with a turn after it, then it
    alone again. No prompt token may be skipped: the recurrent state of a
    skipped prefix would never be computed, and the gaps would show it."""
    rng = np.random.default_rng(3)
    v = cell.config["vocab_size"]
    system = rng.integers(0, v, 32, dtype=np.int32)     # two full pages
    turn = np.concatenate([system, rng.integers(0, v, 9, dtype=np.int32)])
    server = serve.Server(cell, SEED, ref.INIT)
    served = server.serve_now([system], 6)
    served += server.serve_now([turn, system], 12)
    kv = server.session.stats()["kv"]
    assert kv["prefix_reuse"] == "off: recurrent state"
    assert kv["prefill_skipped_tokens"] == 0
    server.close()
    gaps = ref.logit_gaps(cell.config, *_draw(cell, ref), SEED,
                          list(zip([system, turn, system], served)),
                          length=128)
    # bf16 weights and activations against float32: a served token may lie
    # below the reference's best only by rounding, where two logits tie
    assert max(float(g.max()) for g in gaps) < 0.05
    assert sum(g.size for g in gaps) == 30
    assert np.array_equal(served[0][:6], served[2][:6])


def _run(root, capsys, on_server=None):
    rc = run.main(["--workload", BATCH, "--seed", str(SEED), "--seconds",
                   "2", "--trace", "0"], root=root, require_tpu=False,
                  on_server=on_server)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_served_cell_is_correct(root, capsys):
    res = _run(root, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert {"output_tokens_per_s", "setup_s"} <= set(res["metrics"])


def _state_frozen(server):
    """The served path with the mixer's state update taken out: every
    step reads the state the slot was admitted with."""
    from repro.models import blocks, steps
    from repro.runtime import engine

    decode = blocks.BLOCKS["attn_ssm"]["decode"]

    def frozen(cfg, p, x, cache, pos, ctx):
        x, new = decode(cfg, p, x, cache, pos, ctx)
        return x, dict(new, ssm=cache["ssm"])

    blocks.BLOCKS["attn_ssm"]["decode"] = frozen
    try:        # traced and compiled here, while the patch holds
        step = steps.make_decode_step(server.arch,
                                      max_seq=server.serving["max_seq"])
        chunk = engine.make_session_chunk(step, server.serving["chunk"]) \
            .lower(server.params, server.session.state).compile()
    finally:
        blocks.BLOCKS["attn_ssm"]["decode"] = decode
    server.session._chunk_fn = chunk


def test_frozen_state_is_not_correct(root, capsys):
    res = _run(root, capsys, on_server=_state_frozen)
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_is_not_correct(cell):
    """The control (the reference in fp8) in the program's place, through
    the harness's own check, on the requests of a whole run."""
    cellfile = run.cell_file(cell)
    ref = spec.reference_module(cell)
    server = serve.Server(cell, SEED, ref.INIT)
    traffic = run.make_traffic(cell, cellfile, SEED, 2.0)
    run.warm(server, traffic, cell)
    win = serve.drive(server, traffic, seconds=2.0, drain_cap=60.0)
    server.close()
    checks = [run.correctness(cell, SEED, win, ref, cellfile["correct"],
                              control=ctl) for ctl in (False, True)]
    assert run.is_correct(checks[0])
    assert not run.is_correct(checks[1])
    assert checks[1]["max_logit_gap"]["value"] \
        > checks[1]["max_logit_gap"]["limit"]


def _counts():
    mod_spec = importlib.util.spec_from_file_location(
        "counts_falcon_h1", BENCH / "counts" / "falcon_h1.py")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_one_falcon_h1_34b_layer_by_hand():
    c = _counts()
    # q 5120x20x128 and o 20x128x5120; k and v 5120x4x128
    attn = 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128
    # in_proj to z 4096, x 4096, B and C 2x256 each, dt 32; out_proj
    ssm = 5120 * (4096 + 4096 + 512 + 512 + 32) + 4096 * 5120
    mlp = 3 * 5120 * 21504
    assert c.layer_matmul_params(PUBLISHED) == attn + ssm + mlp
    # conv weights and bias over x, B, C (5120 channels, width 4);
    # dt_bias, A_log, D; the gated norm; two block norms
    small = 4 * 5120 + 5120 + 3 * 32 + 4096 + 2 * 5120
    assert c.layer_params(PUBLISHED) == attn + ssm + mlp + small \
        == 430_120_032
    whole = dict(PUBLISHED, num_hidden_layers=72)
    assert 33.6e9 < c.model_params(whole) < 33.7e9
    # 4 MiB of SSM state and 60 KiB of conv tail a layer, read and written
    assert c.state_bytes_per_slot_step(PUBLISHED) \
        == 2 * 4 * 6 * (32 * 128 * 256 + 3 * 5120)
    assert c.bytes_needed(PUBLISHED, 1, 2, 30) == (
        c.step_weight_bytes(PUBLISHED) + 2 * c.slot_step_bytes(PUBLISHED)
        + 30 * c.kv_bytes_per_position(PUBLISHED))
    assert c.state_bytes_needed(PUBLISHED, 3) \
        == 3 * c.state_bytes_per_slot_step(PUBLISHED)


def test_configuration_matches_the_registered_program():
    """Every published width and multiplier that the harness does not
    check itself is the one the program registers."""
    from repro.configs import get

    p, a = PUBLISHED, get("falcon-h1-34b")
    assert (a.ssm_heads, a.ssm_head_dim, a.ssm_state, a.ssm_groups,
            a.conv_width, a.d_ssm) == (
        p["mamba_n_heads"], p["mamba_d_head"], p["mamba_d_state"],
        p["mamba_n_groups"], p["mamba_d_conv"], p["mamba_d_ssm"])
    assert (a.embed_mult, a.lm_head_mult, a.attn_in_mult, a.attn_out_mult,
            a.key_mult, a.ssm_in_mult, a.ssm_out_mult) == (
        p["embedding_multiplier"], p["lm_head_multiplier"],
        p["attention_in_multiplier"], p["attention_out_multiplier"],
        p["key_multiplier"], p["ssm_in_multiplier"],
        p["ssm_out_multiplier"])
    assert list(a.ssm_mults) == p["ssm_multipliers"]
    assert list(a.mlp_mults) == p["mlp_multipliers"]
    assert a.norm_eps == p["rms_norm_eps"] and a.n_layers == 72
    assert not p["mamba_norm_before_gate"] and p["mamba_rms_norm"]


def test_state_floor_reads_the_traced_steps():
    """`ssm_state_floor_pct.batch` on a traced run put together by hand:
    the state bytes of the traced busy slot-steps at the chip's bandwidth
    over the chunk programs' device time; nothing for an architecture
    without recurrent state; a fault where the trace and the probe
    disagree on the chunks run."""
    import types

    from harness import peaks, trace

    read = spec.metric_reader(spec.load_cell(
        "falcon-h1-34b-d6.batch-decode"), "ssm_state_floor_pct.batch")
    busy = np.full(32, 16)
    rows = [(0.0, 0.0, busy)] * 3           # three chunks of 16 steps
    summary = trace.Summary(window_s=4.0, busy_s=3.9,
                            module_busy_s={"jit_chunk_fn(1)": 0.9},
                            module_runs={"jit_chunk_fn(1)": 2},
                            top_ops=[], idle_gaps=[], n_devices=1)
    c = _counts()
    run_ = types.SimpleNamespace(
        cfg=PUBLISHED, counts=c, peak=peaks.peak_for("TPU v5 lite"),
        trace=summary, probe_totals=serve.probe_totals,
        window=types.SimpleNamespace(probe=rows, traced=(0.0, 4.0, 1, 3)))
    want = 100.0 * c.state_bytes_needed(PUBLISHED, 2 * 32 * 16) / 819e9 / 0.9
    assert read(run_) == pytest.approx(want)
    dense = importlib.util.spec_from_file_location(
        "counts_dense_here", BENCH / "counts" / "dense_decoder.py")
    run_.counts = importlib.util.module_from_spec(dense)
    dense.loader.exec_module(run_.counts)
    assert read(run_) is None
    run_.counts = c
    run_.window.traced = (0.0, 4.0, 0, 3)
    with pytest.raises(ValueError):
        read(run_)
