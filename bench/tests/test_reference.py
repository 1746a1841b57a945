"""The plain float32 reference against the program at smoke widths on the
CPU: prefill then decode through the paged session, with a request served
from published prefix pages and one that forks a page copy-on-write. And
the weights the reference draws again are the ones the program got."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from harness import serve, spec, weights


@pytest.fixture(scope="module")
def cell(smoke_root):
    return spec.load_cell("smoke-d2.chat-smoke", smoke_root)


@pytest.fixture(scope="module")
def ref(cell):
    return spec.reference_module(cell)


SEED = 2 ** 33 + 5


def test_reference_draws_the_programs_weights(cell, ref):
    server = serve.Server(cell, SEED, ref.INIT)
    params = server.params
    draw_layer = weights.layer_drawer(ref.layer_shapes(cell.config),
                                      ref.INIT)
    draw_top = weights.layer_drawer(ref.top_shapes(cell.config), ref.INIT)
    flat = {"/".join(str(getattr(k, "key", k)) for k in p): v
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    for layer in range(cell.config["num_hidden_layers"]):
        for path, v in draw_layer(SEED, layer).items():
            assert np.array_equal(np.asarray(flat[path][layer]),
                                  np.asarray(v)), path
    for path, v in draw_top(SEED, 0).items():
        assert np.array_equal(np.asarray(flat[path]), np.asarray(v)), path
    assert set(flat) == set(ref.layer_shapes(cell.config)) | set(
        ref.top_shapes(cell.config))
    server.close()


def test_paged_session_matches_reference_with_prefix_hits(cell, ref):
    rng = np.random.default_rng(3)
    v = cell.config["vocab_size"]
    system = rng.integers(0, v, 48, dtype=np.int32)     # three full pages
    turn = np.concatenate([system, rng.integers(0, v, 9, dtype=np.int32)])
    server = serve.Server(cell, SEED, ref.INIT)
    served = server.serve_now([system], 6)
    served += server.serve_now([turn, system], 12)      # hit, and a fork
    kv = server.session.stats()["kv"]
    assert kv["prefix_hits"] >= 2 and kv["cow_forks"] >= 1
    assert kv["prefill_skipped_tokens"] >= 48 + 47
    server.close()
    reqs = list(zip([system, turn, system], served))
    gaps = ref.logit_gaps(cell.config,
                          weights.layer_drawer(ref.layer_shapes(cell.config),
                                               ref.INIT),
                          weights.layer_drawer(ref.top_shapes(cell.config),
                                               ref.INIT),
                          SEED, reqs, length=128)
    # bf16 weights and activations against float32: a served token may lie
    # below the reference's best only by rounding, where two logits tie
    assert max(float(g.max()) for g in gaps) < 0.1
    assert sum(g.size for g in gaps) == 30
    # the repeated prompt served from a forked page gives the same tokens
    assert np.array_equal(served[0][:6], served[2][:6])


def test_fp8_control_reads_far_above_the_program(cell, ref):
    """The control, the reference computed in fp8, put in the program's
    place: on the same prompts and served tokens, the tokens it puts first
    lie further below the float32 reference's best than the program's."""
    rng = np.random.default_rng(11)
    v = cell.config["vocab_size"]
    prompts = [rng.integers(0, v, n, dtype=np.int32) for n in (20, 40, 70)]
    server = serve.Server(cell, SEED, ref.INIT)
    served = server.serve_now(prompts, 40)
    server.close()
    draw = (weights.layer_drawer(ref.layer_shapes(cell.config), ref.INIT),
            weights.layer_drawer(ref.top_shapes(cell.config), ref.INIT))
    reqs = list(zip(prompts, served))
    prog = max(float(g.max()) for g in ref.logit_gaps(
        cell.config, *draw, SEED, reqs, length=128))
    ctl = max(float(g.max()) for g in ref.logit_gaps(
        cell.config, *draw, SEED, reqs, length=128, control=True))
    print(f"program {prog:.4f} control {ctl:.4f}")
    assert ctl > 3 * prog
    assert ctl > 0.1            # the smoke cells' limit
