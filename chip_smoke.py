"""Bring-up check: the paged serving path, end to end, on a TPU.

One process drives everything (a chip belongs to one process at a time):

1. Device check. Prints the jax version and the devices; exits non-zero
   unless the platform is ``tpu``. There is no CPU path.
2. Kernel phase. The Pallas kernels of the serve path, called through
   ``kernels/ops.py``, compiled for the chip (never interpreted) at
   qwen3-14b widths, each compared with ``kernels/ref.py``.
3. Serve phase. qwen3-14b at its published widths, cut to `DEPTH` of its
   40 layers, random weights from a seed, through the normal entry
   points: ``Cluster`` -> ``ServeSessionProgram(paged=True,
   prefix_cache=True)`` -> ``ServeSession`` with the slot scheduler and
   the paged KV pool; once under the ``tuned`` kernel policy and once
   under ``fused``. Then the same requests through a private-cache
   session, whose tokens must agree with the paged ones.

With ``--chips 4`` it runs only the serving-groups phase instead: the
same requests through ``ShardedServeSessionProgram(groups=4)`` on four
chips and ``groups=1`` on one; per-request tokens must be identical.

Every figure printed on the way is a bring-up figure, not a benchmark
result. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed;
any failure exits non-zero without it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# qwen3-14b keeps every published width; only the depth is cut. 8 layers
# are 5.3 GB of bf16 weights, embed + unembed 3.1 GB more, and the init
# draws each leaf in f32 before the cast: the peak, 13.86 GB of a 16 GB
# chip on a TPU v5e, is that init transient.
ARCH = "qwen3-14b"
DEPTH = 8
SEED = 0

SLOTS = 8
PAGE_SIZE = 16
MAX_SEQ = 1024
CHUNK = 16
MAX_PROMPT = 256
MIN_LEN = 32            # shortest prompt, and shortest answer
MAX_NEW = 64
N_REQUESTS = 16
PREAMBLE = 128          # tokens shared by half of the requests
PREFILL = 2048          # sequence length of the prefill-width kernels
GROUPS = 4              # serving groups of the --chips 4 phase, one a chip

BRING_UP = "[bring-up figure, not a benchmark result]"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------------
# Device check
# ----------------------------------------------------------------------------


def device_check(chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"jax {jax.__version__}")
    print(f"devices: {devices}")
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU backend, found "
                         f"{d0.platform!r}; there is no CPU path")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, found {len(devices)}")
    return d0


def model_config():
    from repro.configs import get
    return dataclasses.replace(get(ARCH), n_layers=DEPTH)


# ----------------------------------------------------------------------------
# Kernel phase
# ----------------------------------------------------------------------------


def _rel_err(got, want) -> float:
    """max |got - want| over max |want|: errors measured in units of the
    largest reference value, the scale one bf16 rounding is relative to."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def kernel_cases(cfg):
    """(op, label, call, args, reference, tolerance, why) per kernel: the
    decode kernels at `SLOTS` rows, the others at `PREFILL` positions.

    Inputs are bf16, as on the serve path; each reference is the ref.py
    oracle evaluated in float32 on the same inputs, so the tolerance only
    has to cover the kernel's own bf16 roundings."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    m_decode, s_prefill = SLOTS, PREFILL
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def rnd(shape, scale=1.0):
        x = jax.random.normal(next(keys), shape, jnp.float32) * scale
        return x.astype(jnp.bfloat16)

    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    x, s, w_up = rnd((m_decode, d)), rnd((d,), 0.1), rnd((d, f), d ** -0.5)
    a, w_down, res = rnd((m_decode, f)), rnd((f, d), f ** -0.5), \
        rnd((m_decode, d))
    q, k, v = (rnd((1, h, s_prefill, hd)), rnd((1, kv, s_prefill, hd)),
               rnd((1, kv, s_prefill, hd)))
    xp = rnd((s_prefill, d))
    g = h // kv
    return [
        ("rmsnorm_matmul", f"({m_decode}x{d})@({d}x{f})", ops.rmsnorm_matmul,
         (x, s, w_up),
         lambda: ref.matmul(ref.rmsnorm(*f32(x, s)), *f32(w_up)),
         2.0 ** -6,
         "two bf16 roundings, of the normalized A tile and of the output "
         "(unit roundoff 2^-8 each), with 2x headroom"),
        ("matmul_residual_add", f"({m_decode}x{f})@({f}x{d})+res",
         ops.matmul_residual_add, (a, w_down, res),
         lambda: ref.matmul(*f32(a, w_down)) + res.astype(jnp.float32),
         2.0 ** -6,
         "two bf16 roundings, of the matmul output before the f32 "
         "residual add and of the sum (2^-8 each), with 2x headroom"),
        ("flash_attention", f"q(1,{h},{s_prefill},{hd}) kv_heads={kv}",
         lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
         (q, k, v),
         lambda: ref.flash_attention(
             *f32(q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)),
             causal=True),
         2.0 ** -5,
         "bf16 probabilities into the PV matmul, unnormalized (online "
         "softmax, rescaled as the row maximum moves), and a bf16 output: "
         "a few 2^-8 roundings, with headroom"),
        ("rmsnorm", f"({s_prefill}x{d})", ops.rmsnorm, (xp, s),
         lambda: ref.rmsnorm(*f32(xp, s)),
         2.0 ** -7,
         "f32 arithmetic on both sides and one bf16 rounding of the "
         "output (2^-8), with 2x headroom"),
    ]


def kernel_phase(cfg) -> None:
    import jax

    from repro.cluster.policy import current_policy

    for op, label, call, args, reference, tol, why in kernel_cases(cfg):
        check(not current_policy().interpret_for(op),
              f"{op} would run interpreted on the chip")
        compiled = jax.jit(call).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{op}: no Pallas kernel (tpu_custom_call) in the compiled "
              f"program")
        out = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(reference())
        err = _rel_err(out, want)
        check(bool(np.isfinite(np.asarray(out, np.float32)).all()),
              f"{op}: non-finite output")
        print(f"kernel {op} {label}: compiled (tpu_custom_call, not "
              f"interpreted); max|err|/max|ref| = {err:.3e} <= {tol:.3e} "
              f"({why})")
        check(err <= tol, f"{op}: error {err:.3e} above tolerance {tol:.3e}")


# ----------------------------------------------------------------------------
# Serve phase
# ----------------------------------------------------------------------------


def workload(vocab: int) -> list[tuple[np.ndarray, int]]:
    """`N_REQUESTS` seeded (prompt, max_new) pairs; the even ones share a
    `PREAMBLE`-token prefix.

    Prompts run from `MIN_LEN` tokens to `MAX_PROMPT`, answers from
    `MIN_LEN` to `MAX_NEW`. The first `SLOTS` requests fill the pool;
    among them the prefix-sharing ones are the shortest, so they finish,
    and publish the preamble's pages, before any request of the second
    wave is admitted: prefix-cache hits are certain, not a matter of
    timing."""
    rng = np.random.default_rng(SEED)
    pre = rng.integers(0, vocab, PREAMBLE)
    reqs = []
    for i in range(N_REQUESTS):
        first_wave = i < SLOTS
        if i % 2 == 0:
            tail = rng.integers(0, vocab, rng.integers(
                0, (MAX_PROMPT - PREAMBLE) // (4 if first_wave else 1) + 1))
            prompt = np.concatenate([pre, tail])
            new = (MIN_LEN if first_wave
                   else int(rng.integers(MIN_LEN, MAX_NEW + 1)))
        else:
            size = (rng.integers(MAX_PROMPT * 3 // 4, MAX_PROMPT + 1)
                    if first_wave else rng.integers(MIN_LEN, MAX_PROMPT + 1))
            prompt = rng.integers(0, vocab, size)
            new = int(rng.integers(MIN_LEN, MAX_NEW + 1))
        reqs.append((prompt.astype(np.int32), new))
    return reqs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def serve(program, params, reqs, label: str,
          clock: CompileClock) -> tuple[list, dict]:
    """Drive one session over `reqs`; returns each request's tokens and
    the session's stats."""
    import jax

    c0 = clock.seconds
    t0 = time.perf_counter()
    sess = program.open(params=params)
    held = sum((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.devices())
    handles = [sess.submit(p, n) for p, n in reqs]
    st = sess.drain()
    wall = time.perf_counter() - t0
    sess.close()
    failed = [(h.id, h.fail_reason) for h in handles if not h.ok]
    groups = st.get("groups", {0: st})
    nan_trips = sum(g["retries"] for g in groups.values())
    kv = st.get("kv", {})
    print(f"serve {label}: {len(handles)} requests, "
          f"{st['emitted_total']} tokens in {wall:.2f} s "
          f"({st['emitted_total'] / wall:.1f} tok/s wall clock) "
          f"{BRING_UP}")
    print(f"serve {label}: TTFT p50 {st['ttft_ms']['p50']:.1f} ms p99 "
          f"{st['ttft_ms']['p99']:.1f} ms; stall "
          f"{st['stall']['stall_pct']:.2f}%; compile "
          f"{clock.seconds - c0:.1f} s; bytes_in_use with the session "
          f"open {held} (all chips) {BRING_UP}")
    if kv:
        print(f"serve {label}: prefix hits {kv.get('prefix_hits')}, "
              f"prefill tokens skipped {kv.get('prefill_skipped_tokens')}, "
              f"pages shared {kv.get('pages_shared')}")
    check(not failed, f"{label}: failed requests {failed}")
    check(nan_trips == 0, f"{label}: {nan_trips} NaN-sentinel trips")
    check(st["requests_done"] == len(reqs),
          f"{label}: {st['requests_done']} of {len(reqs)} requests done")
    for h, (p, n) in zip(handles, reqs):
        check(h.tokens.size == n, f"{label}: request {h.id} emitted "
              f"{h.tokens.size} of {n} tokens")
    return [h.result() for h in handles], st


def agreement(a: list, b: list, label: str) -> float:
    """Share of requests whose tokens are identical; prints where the
    first disagreeing request first differs."""
    same = [np.array_equal(x, y) for x, y in zip(a, b)]
    share = sum(same) / len(same)
    print(f"{label}: {share:.4f} of requests agree token for token")
    if share < 1.0:
        i, step = first_difference(a, b)
        print(f"{label}: request {i} first differs at generated token "
              f"{step}: {a[i][step:step + 4].tolist()} vs "
              f"{b[i][step:step + 4].tolist()}")
    return share


def first_difference(a: list, b: list) -> tuple[int, int]:
    """(request, generated-token step) where `a` and `b` first differ;
    (0, 0) when they agree throughout."""
    for i, (x, y) in enumerate(zip(a, b)):
        n = min(x.size, y.size)
        step = next((t for t in range(n) if x[t] != y[t]), n)
        if step < n:
            return i, step
    return 0, 0


# max ||fused - tuned|| / ||tuned|| over the logits of one decode step. The
# two policies compute the same function with their bf16 roundings in other
# places (the fused prologue rounds the normalized A tile, the epilogue adds
# the residual in f32 before its one rounding), so per layer the hidden state
# moves by a few bf16 unit roundoffs (2^-8) of its size; over `DEPTH` layers
# that stays below 2^-6, and the bound leaves 4x on top. A wiring fault (a
# lost residual, a misapplied norm scale, a transposed weight) moves the
# logits by O(1) of their norm.
FUSED_LOGITS_TOL = 2.0 ** -4


def fused_logits_check(cfg, params, prompt, generated) -> None:
    """One decode step under `tuned` and under `fused` from the same cache:
    `prompt` and then `generated` fed through the tuned step, so the step
    compared is the one where the served tokens first differ. Their
    logits may differ by rounding only (`FUSED_LOGITS_TOL`)."""
    import jax
    import jax.numpy as jnp

    from repro.models import steps

    step = {mode: jax.jit(steps.make_decode_step(
        cfg, max_seq=MAX_SEQ, policy=mode, logits=True))
        for mode in ("tuned", "fused")}
    feed = np.concatenate([prompt, generated]).astype(np.int32)
    cache = steps.init_cache(cfg, 1, steps.decode_cache_len(cfg, MAX_SEQ))

    def batch(t):
        return {"tokens": jnp.asarray(feed[t:t + 1][None]),
                "pos": jnp.asarray(t, jnp.int32)}

    for t in range(feed.size - 1):
        cache, _ = step["tuned"](params, cache, batch(t))
    out = {mode: np.asarray(fn(params, cache, batch(feed.size - 1))[1][0])
           for mode, fn in step.items()}
    tuned, fused = out["tuned"], out["fused"]
    rel = float(np.linalg.norm(fused - tuned) / np.linalg.norm(tuned))
    top = np.argsort(tuned)[::-1][:2]
    print(f"tuned vs fused logits, one decode step at position "
          f"{feed.size - 1}: ||fused - tuned|| / ||tuned|| = {rel:.3e} <= "
          f"{FUSED_LOGITS_TOL:.3e}; max|fused - tuned| "
          f"{np.max(np.abs(fused - tuned)):.3e}; tuned top-2 gap "
          f"{tuned[top[0]] - tuned[top[1]]:.3e} (tokens {top.tolist()}), "
          f"fused argmax {int(np.argmax(fused))}")
    check(bool(np.isfinite(fused).all()), "fused logits not finite")
    check(rel <= FUSED_LOGITS_TOL, f"fused decode logits off by {rel:.3e} "
          f"of their norm, above {FUSED_LOGITS_TOL:.3e}")


def session_spec(**kw) -> dict:
    return dict(slots=SLOTS, max_seq=MAX_SEQ, max_prompt=MAX_PROMPT,
                chunk=CHUNK, seed=SEED, nan_check=True, preempt=False, **kw)


def serve_phase(cfg) -> None:
    from repro.cluster import Cluster, ServeSessionProgram

    clock = CompileClock()
    reqs = workload(cfg.vocab)
    cluster = Cluster(cfg)
    common = session_spec()
    paged = ServeSessionProgram(paged=True, page_size=PAGE_SIZE,
                                prefix_cache=True, **common)
    programs = {}
    for mode in ("tuned", "fused"):
        with cluster.policy(mode):
            programs[mode] = cluster.compile(paged)
    with cluster.policy("tuned"):
        private = cluster.compile(ServeSessionProgram(**common))
    print(f"model {cfg.name} depth {cfg.n_layers}/40: d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads of {cfg.hd}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, qk_norm {cfg.qk_norm}, "
          f"rope theta {cfg.rope_theta:g}")
    params = programs["tuned"].init_params()
    tokens = {}
    for mode, program in programs.items():
        tokens[mode], st = serve(program, params, reqs, f"paged/{mode}",
                                 clock)
        check(st["kv"]["prefix_hits"] > 0,
              f"paged/{mode}: no prefix-cache hits")
    tokens["private"], _ = serve(private, params, reqs, "private/tuned",
                                 clock)
    agreement(tokens["tuned"], tokens["fused"],
              "tuned vs fused kernels, paged (tokens may differ at near "
              "ties; the logits are checked below)")
    i, step = first_difference(tokens["tuned"], tokens["fused"])
    fused_logits_check(cfg, params, reqs[i][0], tokens["tuned"][i][:step])
    share = agreement(tokens["tuned"], tokens["private"],
                      "paged vs private cache, tuned")
    check(share == 1.0, "paged and private-cache sessions disagree")


# ----------------------------------------------------------------------------
# Four chips: serving groups
# ----------------------------------------------------------------------------


def groups_phase(cfg) -> None:
    """The same requests through `groups=GROUPS` on that many chips and
    through `groups=1` on one; tokens must match request for request."""
    import jax

    from repro.cluster import Cluster, ShardedServeSessionProgram
    from repro.core import compat

    clock = CompileClock()
    reqs = workload(cfg.vocab)
    spec = session_spec(paged=True, page_size=PAGE_SIZE, prefix_cache=True)
    devices = jax.devices()[:GROUPS]
    tokens = {}
    for g, devs in ((1, devices[:1]), (GROUPS, devices)):
        # one cluster per run, released before the next: each run draws
        # its params on its own chips, and chip 0 holds one copy at a time
        cluster = Cluster(cfg, mesh=compat.make_mesh(
            (len(devs), 1), ("data", "model"), devices=devs))
        program = cluster.compile(ShardedServeSessionProgram(groups=g,
                                                             **spec))
        tokens[g], st = serve(program, None, reqs,
                              f"groups={g} on {len(devs)} chip(s)", clock)
        print(f"groups={g}: requests placed per group "
              f"{st['placement']['placed']}")
        del cluster, program
        gc.collect()
    share = agreement(tokens[1], tokens[GROUPS],
                      f"groups={GROUPS} vs groups=1")
    check(share == 1.0, f"groups={GROUPS} and groups=1 disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, GROUPS), default=1,
                    help=f"{GROUPS}: run only the serving-groups phase on "
                         f"{GROUPS} chips")
    args = ap.parse_args(argv)
    d0 = device_check(args.chips)

    from repro.runtime.compile_cache import use_persistent_cache

    print(f"compile cache: {use_persistent_cache()}")
    cfg = model_config()
    phases = ([groups_phase] if args.chips == GROUPS
              else [kernel_phase, serve_phase])
    t0 = time.perf_counter()
    try:
        for phase in phases:
            phase(cfg)
            print(f"{phase.__name__} passed at "
                  f"{time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        raise SystemExit(f"chip_smoke: FAILED: {e}")
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
