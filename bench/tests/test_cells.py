"""Whole runs on the CPU at smoke widths, in a checkout whose smoke cells,
configuration, traffic mixes and one per-layer metric were added as files
and entries only. The run's look for a chip is skipped; everything else
is the run the driver makes, and `correct` has to come out false when the
served path is broken underneath."""

from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

import run
from harness import spec

SEED = 2 ** 31 + 4242
CHAT, BATCH = "smoke-d2.chat-smoke", "smoke-d2.batch-smoke"


def run_cell(root, capsys, cell, trace=0, on_server=None, seconds=2):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_tpu=False, on_server=on_server)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0

    def strict(c):
        raise AssertionError(f"{c} is not JSON")
    return json.loads(out[-1], parse_constant=strict)


def test_added_cell_loads_from_data_files(smoke_root):
    cell = spec.load_cell(CHAT, smoke_root)
    assert cell.config_name == "smoke-d2"
    assert cell.traffic["kind"] == "sessions"
    assert "served_tokens.smoke" in [m["name"] for m in cell.per_layer]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "tpot_p90_ms", "setup_s"}


@pytest.mark.parametrize("cell,trace", [(CHAT, 0), (CHAT, 1), (BATCH, 0),
                                        (BATCH, 1)])
def test_run_on_cpu(smoke_root, capsys, cell, trace):
    res = run_cell(smoke_root, capsys, cell, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["kind"] and res["device"]["count"] >= 1
    assert list(res)[-1] == "check"
    names = set(res["metrics"])
    if trace:
        want = ({"step_ms.chat", "ttft_p90_ms.chat"} if cell == CHAT
                else {"step_ms.batch"})
        assert want <= names
        if cell == CHAT:
            assert res["metrics"]["served_tokens.smoke"]["value"] > 0
    else:
        assert "setup_s" in names
        assert ("ttft_mean_ms" in names) == (cell == CHAT)
        assert ("output_tokens_per_s" in names) == (cell == BATCH)


def test_refuses_a_device_that_is_not_a_tpu(smoke_root, capsys):
    rc = run.main(["--workload", CHAT, "--seed", "1", "--seconds", "1"],
                  root=smoke_root)
    assert rc != 0
    assert capsys.readouterr().out == ""


def _state_unchanged(server):
    def chunk(params, state):
        n = state["tok"].shape[0]
        z = jnp.zeros((n, server.serving["chunk"]), jnp.int32)
        return (state, z, z.astype(bool), jnp.zeros((n,), jnp.int32),
                jnp.asarray(False))
    server.session._chunk_fn = chunk


def _token_altered(server):
    inner = server.session._chunk_fn
    vocab = server.cell.config["vocab_size"]

    def chunk(params, state):
        out = inner(params, state)
        return (out[0], (out[1] + 1) % vocab) + tuple(out[2:])
    server.session._chunk_fn = chunk


@pytest.mark.parametrize("cell", [CHAT, BATCH])
@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_broken_served_path_is_not_correct(smoke_root, capsys, cell, fault):
    res = run_cell(smoke_root, capsys, cell, on_server=fault)
    assert res["correct"] is False


@pytest.mark.parametrize("cell", [CHAT, BATCH])
def test_control_is_not_correct(smoke_root, cell):
    """The control (the reference in fp8, the next precision down) put in
    the program's place, through the harness's own check, on the requests
    of a whole run: it has to come out not correct where the program
    comes out correct."""
    from harness import serve

    c = spec.load_cell(cell, smoke_root)
    cellfile = run.cell_file(c)
    ref = spec.reference_module(c)
    server = serve.Server(c, SEED, ref.INIT)
    traffic = run.make_traffic(c, cellfile, SEED, 2.0)
    run.warm(server, traffic, c)
    win = serve.drive(server, traffic, seconds=2.0,
                      drain_cap=float(c.traffic.get("drain_cap_s", 60.0)))
    server.close()
    checks = [run.correctness(c, SEED, win, ref, cellfile["correct"],
                              control=ctl) for ctl in (False, True)]
    assert run.is_correct(checks[0])
    assert not run.is_correct(checks[1])
    assert checks[1]["max_logit_gap"]["value"] \
        > checks[1]["max_logit_gap"]["limit"]
