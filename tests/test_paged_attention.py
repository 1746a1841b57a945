"""The paged decode attention kernel against its reference, interpreted on
the CPU: the gather-then-mask composition (`paged_gather` +
`decode_attention`) that the "reference" policy routes to.

Widths are the served models' attention widths at hd 128 (qwen3-14b 40/8,
yi-34b 56/8, Falcon-H1-34B 20/4), reduced in slots and pages. Pools are
bf16 as served. The kernel normalises once at the end of its online
softmax, where the reference casts the normalised weights to bf16, so the
two differ by bf16 rounding: outputs lie within 0.02 of each other (one
bf16 step at magnitudes 2-4, where these averages of N(0, 1) rows can
reach).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.policy import KernelPolicy, use_policy
from repro.kernels import ops

PS, HD = 16, 128
NPP = 129                         # max_seq 2048 plus a chunk, at page 16
LENGTHS = [1, 15, 16, 17, 470, NPP * PS]
WIDTHS = [(40, 8), (56, 8), (20, 4)]
ATOL = 0.02
REFERENCE = KernelPolicy(overrides={"paged_decode_attention": "reference"})


def _operands(h, kv, n_layers=1, seed=0):
    """Pools of `n_layers` regions (layer l's pages are l * n_pages + p,
    page 0 of each region its trash page) and per-slot tables of distinct
    shuffled pages."""
    b = len(LENGTHS)
    n_pages = 1 + b * NPP
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (n_layers * n_pages, PS, kv, HD)
    k_pool = jax.random.normal(ks[0], pool, jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], pool, jnp.bfloat16)
    q = jax.random.normal(ks[2], (b, 1, h, HD), jnp.bfloat16)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_pages))
    pages = jnp.asarray(perm.reshape(b, NPP), jnp.int32)
    return q, k_pool, v_pool, jnp.asarray(LENGTHS, jnp.int32), pages, n_pages


def _kernel(*args):
    with use_policy(KernelPolicy()):
        return np.asarray(ops.paged_decode_attention(*args), np.float32)


def _reference(*args):
    with use_policy(REFERENCE):
        return np.asarray(ops.paged_decode_attention(*args), np.float32)


@pytest.mark.parametrize("h,kv", WIDTHS)
def test_matches_reference_at_every_length(h, kv):
    """Lengths 1, 15, 16, 17 (around a page boundary), a mid value and the
    whole table, in one batch."""
    q, k_pool, v_pool, lengths, pages, _ = _operands(h, kv)
    got = _kernel(q, k_pool, v_pool, lengths, pages)
    want = _reference(q, k_pool, v_pool, lengths, pages)
    assert got.shape == (len(LENGTHS), 1, h, HD)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("h,kv", WIDTHS)
def test_layer_offset_tables_read_their_own_region(h, kv):
    """Inside the decode step's layer scan the pool is one flat buffer of
    every layer's region and layer l's table is `pages + l * n_pages`: the
    kernel reads layer l's rows, as the reference does on that region
    alone."""
    n_layers = 3
    q, k_pool, v_pool, lengths, pages, n_pages = _operands(h, kv, n_layers)
    for layer in range(n_layers):
        region = slice(layer * n_pages, (layer + 1) * n_pages)
        got = _kernel(q, k_pool, v_pool, lengths, pages + layer * n_pages)
        want = _reference(q, k_pool[region], v_pool[region], lengths, pages)
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0,
                                   err_msg=f"layer {layer}")


def test_slot_on_the_trash_page():
    """A retired slot's table points every entry at the trash page and its
    length stays frozen: the kernel reads the trash page only, as the
    reference does."""
    q, k_pool, v_pool, lengths, pages, _ = _operands(*WIDTHS[0], seed=1)
    pages = pages.at[2].set(0).at[4].set(0)
    got = _kernel(q, k_pool, v_pool, lengths, pages)
    want = _reference(q, k_pool, v_pool, lengths, pages)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("h,kv", WIDTHS)
def test_nan_past_the_length_never_reaches_the_output(h, kv):
    """Every page past each slot's last live page holds NaN: the kernel's
    output is finite and the same as over a clean pool. (The reference
    gathers those pages, and 0 * NaN reaches its output.)"""
    q, k_pool, v_pool, lengths, pages, _ = _operands(h, kv, seed=2)
    clean = _kernel(q, k_pool, v_pool, lengths, pages)
    n_live = -(-np.asarray(LENGTHS) // PS)
    tables = np.asarray(pages)
    dead = np.concatenate([tables[b, n:] for b, n in enumerate(n_live)])
    k_nan = k_pool.at[dead].set(jnp.nan)
    v_nan = v_pool.at[dead].set(jnp.nan)
    got = _kernel(q, k_nan, v_nan, lengths, pages)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert not np.isfinite(_reference(q, k_nan, v_nan, lengths, pages)).all()


def test_blocking_follows_the_shapes():
    """Pages per block come from the pool's shapes alone: 2048 K rows a
    block, 16 pages at KV 8 and 32 at KV 4, never more than a table."""
    from repro.kernels.paged_attention import default_pages_per_block
    assert default_pages_per_block(PS, 8, NPP) == 16
    assert default_pages_per_block(PS, 4, NPP) == 32
    assert default_pages_per_block(4, 2, 12) == 12
    assert default_pages_per_block(PS, 256, NPP) == 1
