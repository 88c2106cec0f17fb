"""Serve granite-3-2b at its published widths on one TPU chip, end to end.

It drives the path a user calls — SecureGateway -> scheduler -> sealed
paged KV pool -> PagedEngine — with random weights made from a seed:

  serve    3 tenants send 6 requests (64-256-token prompts, 16 new tokens
           each) to a trusted gateway; every request must finish with 16
           unpoisoned tokens, and launches must be verified
  tamper   the same prompts again, with one ciphertext word of one
           request's KV page flipped mid-flight: that request alone is
           poisoned, every other one repeats its clean tokens
  off      the trusted gateway is freed and the same prompts go through a
           security="off" gateway: the greedy tokens must be identical,
           since sealing is a bit-exact XOR
  kernels  the four Pallas kernels, compiled, at granite widths: each
           result must equal its ref.py oracle bit for bit

No phase's failure is caught.  Run from the repository root on a machine
with one TPU:

    python3 chip_smoke.py

The last line of stdout is {"ok": true, "device": {...}}.  Without a TPU,
or without the repository beside it, the script exits non-zero and prints
no result.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "granite-3-2b"
SEED = 0


@dataclasses.dataclass(frozen=True)
class Workload:
    """The requests the gateway phases send."""
    tenants: tuple = ("alice", "bob", "carol")
    n_requests: int = 6
    min_prompt: int = 64
    max_prompt: int = 256
    max_new: int = 16
    page_size: int = 16
    n_pages: int = 256
    slots: int = 4

    @property
    def max_pages(self) -> int:
        return -(-(self.max_prompt + self.max_new) // self.page_size)

    def prompts(self, vocab: int) -> list:
        """[(tenant, int32 prompt)], the same for every call."""
        import numpy as np
        rng = np.random.RandomState(SEED)
        return [(self.tenants[i % len(self.tenants)],
                 rng.randint(0, vocab, rng.randint(self.min_prompt,
                                                   self.max_prompt + 1))
                 .astype(np.int32))
                for i in range(self.n_requests)]


def expect(ok: bool, what) -> None:
    """A check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def init_params(cfg):
    import jax
    from repro.models import registry
    return registry.init_params(jax.random.PRNGKey(SEED), cfg)


def build_gateway(cfg, security: str, wl: Workload):
    """A gateway over freshly made weights.  A trusted gateway seals them
    into their own buffers and keeps only the sealed copy: no plaintext
    weight may outlive the constructor."""
    import jax
    from repro.serve import SecureGateway
    before = {id(a) for a in jax.live_arrays()}
    gw = SecureGateway(cfg, init_params(cfg), security=security,
                       max_slots=wl.slots, page_size=wl.page_size,
                       n_pages=wl.n_pages, max_pages_per_seq=wl.max_pages,
                       donate_params=True)
    if security == "trusted":
        gc.collect()
        plain = {(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_params(cfg)))}
        held = [(a.shape, a.dtype) for a in jax.live_arrays()
                if id(a) not in before and (a.shape, a.dtype) in plain]
        expect(not held, f"plaintext weights still on the device: {held}")
    return gw


def serve_phase(gw, wl: Workload, prompts) -> list:
    """Submit every prompt, drain, check each request; -> token lists."""
    from repro.serve import TOKEN_POISON
    rids = [gw.submit(t, p, max_new=wl.max_new) for t, p in prompts]
    gw.drain()
    out = []
    for rid in rids:
        toks = gw.collect(rid).tolist()
        expect(gw.status(rid) == "done", (rid, gw.status(rid)))
        expect(len(toks) == wl.max_new, (rid, toks))
        expect(TOKEN_POISON not in toks, (rid, toks))
        expect(all(0 <= t < gw.cfg.vocab for t in toks), (rid, toks))
        out.append(toks)
    if gw.pool.sealed:                      # "off" verifies no launches
        expect(gw.metrics()["launches_verified"] > 0, "no launch verified")
    return out


def tamper_phase(gw, wl: Workload, prompts, clean: list) -> None:
    """Flip one ciphertext word in a page of the first request once it is
    prefilled; only that request may be poisoned."""
    from repro.serve import TOKEN_POISON
    rids = [gw.submit(t, p, max_new=wl.max_new) for t, p in prompts]
    gw.step()                                     # admit + prefill
    victim = rids[0]
    page = gw.scheduler.requests[victim].pages[0]
    idx = gw.pool.elem_index(page, 0, 0, 0, 0)
    gw.pool.k_ct = gw.pool.k_ct.at[idx].add(1)
    gw.drain()
    for i, rid in enumerate(rids):
        toks = gw.collect(rid).tolist()
        if rid == victim:
            expect(gw.status(rid) == "poisoned", (rid, gw.status(rid)))
            expect(toks[-1] == TOKEN_POISON, (rid, toks))
        else:
            expect(gw.status(rid) == "done", (rid, gw.status(rid)))
            expect(toks == clean[i], (rid, toks, clean[i]))
    expect(gw.pool.live_pages == 0, "pages left allocated")


def off_phase(gw, wl: Workload, prompts, trusted: list) -> list:
    """The same prompts through a security="off" gateway: same tokens."""
    off = serve_phase(gw, wl, prompts)
    for i, (a, b) in enumerate(zip(trusted, off)):
        expect(a == b, f"request {i}: trusted {a} != off {b}")
    return off


# ---------------------------------------------------------------------------
# kernels, compiled at granite widths
# ---------------------------------------------------------------------------

def _kernel_cases(cfg):
    """name -> (fn(backend, *args), args): each kernel's ops wrapper, run
    as "pallas" (the compiled kernel) and as "jnp" (its ref.py oracle).

    Float inputs are chosen so that every sum is exact in f32: integer
    matmul operands, and attention scores that put the whole softmax on
    one cached position.  The two paths then agree bit for bit whatever
    order they add in.  Each sealed case has one tampered word, so the
    mismatch counts are compared too."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ctr_cipher import ops as ctr_ops
    from repro.kernels.sealed_attention import ops as attn_ops
    from repro.kernels.sealed_matmul import ops as mm_ops
    from repro.kernels.tree_mac import ops as mac_ops

    rng = np.random.RandomState(SEED)
    key = jnp.asarray([0x5EA1, 0xC0DE], jnp.uint32)
    D, F, K, hd = cfg.d_model, cfg.d_ff, cfg.n_kv_heads, cfg.hd
    G = cfg.n_heads // K
    cases = {}

    # a [d_model, d_ff] bf16 weight as its 32-bit word lattice
    words = jnp.asarray(rng.randint(0, 2**32, (D, F // 2), np.uint64)
                        .astype(np.uint32))
    cases["ctr_cipher"] = (
        lambda be, x: ctr_ops.ctr_xor(x, key, backend=be), (words,))
    cases["tree_mac"] = (
        lambda be, x: mac_ops.mac_tags(x, key, 128, backend=be), (words,))

    # 256 activation rows times the [d_model, d_ff] up projection
    a = jnp.asarray(rng.randint(-2, 3, (256, D)), jnp.bfloat16)
    b = jnp.asarray(rng.randint(-2, 3, (D, F)), jnp.bfloat16)
    a_ct, ta = mm_ops.seal_operand(a, key, 7, mm_ops.BK // 2, mac_nonce=7)
    b_ct, tb = mm_ops.seal_operand(b, key, 8, mm_ops.BK // 2, mac_nonce=7)
    cases["sealed_matmul"] = (
        lambda be, *x: mm_ops.matmul(*x, key, 7, 8, backend=be),
        (a_ct, b_ct.at[100, 300].add(1), ta, tb))

    # decode attention over a 2048-token cache: every query head scores
    # 512 at one valid position and 0 elsewhere, so softmax is one-hot;
    # the decoy past t_valid must stay masked
    B, T, t_valid = 4, 2048, 1500
    q = jnp.full((B, K, G, hd), 8, jnp.bfloat16)
    kc = np.zeros((B, T, K, hd), np.float32)
    kc[np.arange(B)[:, None], rng.randint(0, t_valid, (B, K)),
       np.arange(K)[None, :]] = 8
    kc[:, t_valid + 7] = 8
    v = jnp.asarray(rng.randn(B, T, K, hd), jnp.bfloat16)
    k_ct, v_ct, k_tags, v_tags = attn_ops.seal_cache(
        jnp.asarray(kc, jnp.bfloat16), v, key, 11, 12)
    cases["sealed_attention"] = (
        lambda be, *x: attn_ops.decode_attention(*x, key, 11, 12, t_valid,
                                                 backend=be),
        (q, k_ct, v_ct.at[1, 9, 1, 5].add(1), k_tags, v_tags))
    return cases


def kernel_phase(cfg, log=print) -> None:
    import functools
    import jax
    import numpy as np
    for name, (fn, args) in _kernel_cases(cfg).items():
        t0 = time.perf_counter()
        compiled = jax.jit(functools.partial(fn, "pallas")).lower(
            *args).compile()
        t1 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        t2 = time.perf_counter()
        want = jax.jit(functools.partial(fn, "jnp"))(*args)
        got, want = jax.tree_util.tree_leaves(got), \
            jax.tree_util.tree_leaves(want)
        expect(len(got) == len(want), name)
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            expect(g.shape == w.shape and g.dtype == w.dtype,
                   (name, g.shape, w.shape, g.dtype, w.dtype))
            expect(g.tobytes() == w.tobytes(),
                   f"{name}: the kernel differs from its ref")
        log(f"kernel {name}: compile {t1 - t0:.2f} s, run {t2 - t1:.4f} s, "
            f"equal to ref bit for bit; inputs "
            f"{[tuple(x.shape) for x in args]}")


# ---------------------------------------------------------------------------

def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def main() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the repository's src/repro is not beside {ROOT / 'chip_smoke.py'}")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (device 0 is {dev.platform}); this script "
              "runs on the chip only")
    from repro import configs
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()

    def peak() -> str:
        return f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}"

    cfg = configs.get_config(ARCH)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(cfg))))
    print(f"device {dev.platform} / {dev.device_kind} x {len(jax.devices())}")
    print(f"config {cfg.arch_id}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.hd}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.param_dtype}; "
          f"{n_params} parameters")
    wl = Workload()
    prompts = wl.prompts(cfg.vocab)
    print(f"workload: {wl}; prompt lengths {[len(p) for _, p in prompts]}")

    t0 = time.perf_counter()
    gw = build_gateway(cfg, "trusted", wl)
    jax.block_until_ready(gw.engine.params)
    print(f"build trusted gateway (weights made and sealed): "
          f"{time.perf_counter() - t0:.1f} s; {peak()}")

    t0 = time.perf_counter()
    trusted = serve_phase(gw, wl, prompts)
    print(f"serve trusted: {time.perf_counter() - t0:.1f} s with compiles; "
          f"{peak()}")
    for (tenant, p), toks in zip(prompts, trusted):
        print(f"  {tenant} prompt {len(p)}: {toks}")
    m = gw.metrics()
    print(f"  launches verified {m['launches_verified']}, kv pages peak "
          f"{m['kv_pages_peak']}, page closes {m['page_closes']}")

    t0 = time.perf_counter()
    tamper_phase(gw, wl, prompts, trusted)
    print(f"tamper: request 0 ({prompts[0][0]}) poisoned, the other "
          f"{len(prompts) - 1} repeat their tokens; "
          f"{time.perf_counter() - t0:.1f} s warm; {peak()}")

    del gw
    gc.collect()
    print(f"trusted gateway freed: bytes_in_use "
          f"{dev.memory_stats()['bytes_in_use']}")
    t0 = time.perf_counter()
    gw = build_gateway(cfg, "off", wl)
    jax.block_until_ready(gw.engine.params)
    print(f"build off gateway (weights made): "
          f"{time.perf_counter() - t0:.1f} s; {peak()}")
    t0 = time.perf_counter()
    off_phase(gw, wl, prompts, trusted)
    print(f"off: {len(prompts)} requests, tokens identical to trusted; "
          f"{time.perf_counter() - t0:.1f} s with compiles; {peak()}")
    del gw
    gc.collect()

    kernel_phase(cfg)
    print(f"kernels: {peak()}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
