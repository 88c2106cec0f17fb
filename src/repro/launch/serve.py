"""Serving launcher — multi-tenant secure gateway (continuous batching).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --tenants 3 --requests 6 --max-new 12

Each tenant runs its own §3.2 attestation handshake; requests have mixed
prompt lengths and share one sealed paged KV pool.  ``--hi-every N`` marks
every Nth request as high priority (class 5): when slots or pages run out it
preempts running low-priority requests, whose sealed KV swaps verbatim into
the SealedStore host tier and back.  ``--engine fixed`` keeps the legacy
equal-length fixed-slot path for comparison.

``--watch N`` prints the live posture dashboard (SLOs, alerts, per-tenant
state — obs/dash.py) to stderr every N steps; ``--slo name=value`` tunes
the streaming Monitor's thresholds, e.g. ``--slo ttft_p95_ms=250``.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

import jax
import numpy as np

from .. import configs
from ..core.channel import SecureChannel
from ..models import registry
from ..obs import MonitorConfig, parse_slo_overrides, render_gateway
from ..serve import SecureGateway, ServeEngine

REPO = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Keep compiled programs across runs in ``<repo>/.jax_cache``, unless
    JAX_COMPILATION_CACHE_DIR names a directory (jax reads it itself).  A
    fixed path: the cache directory is part of what a cached entry matches.
    Entry points call this; importing the library never does."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


def _run_gateway(cfg, params, args) -> None:
    mon_cfg = MonitorConfig()
    if args.slo:
        mon_cfg = mon_cfg.overridden(**parse_slo_overrides(args.slo))
    gw = SecureGateway(cfg, params, security=args.security,
                       max_slots=args.slots, page_size=args.page_size,
                       n_pages=args.pages, max_pages_per_seq=args.max_pages,
                       rotate_every=args.rotate_every,
                       open_pages=not args.whole_page_reseal,
                       prefill_chunk=args.prefill_chunk,
                       trace=bool(args.trace),
                       monitor_config=mon_cfg, donate_params=True)
    rng = np.random.RandomState(0)
    rids = []
    for i in range(args.requests):
        tenant = f"tenant-{i % args.tenants}"
        plen = int(rng.randint(args.min_prompt, args.max_prompt + 1))
        prompt = rng.randint(0, cfg.vocab, plen)
        prio = 5 if (args.hi_every and (i + 1) % args.hi_every == 0) else 0
        rids.append(gw.submit(tenant, prompt, max_new=args.max_new,
                              priority=prio))
    if args.watch:
        # periodic posture snapshot to stderr while draining (the same
        # renderer tools/obs_dash.py runs offline)
        steps = 0
        while not gw.scheduler.idle:
            gw.step()
            steps += 1
            if steps % args.watch == 0:
                print(render_gateway(gw), file=sys.stderr)
        print(render_gateway(gw), file=sys.stderr)
    else:
        gw.drain()
    for rid in rids:
        out = gw.collect(rid)
        req = gw.scheduler.requests[rid]
        swaps = f" swaps {req.swaps_out}/{req.swaps_in}" if req.swaps_out \
            else ""
        print(f"  req {rid} [{req.tenant_id}, prompt {req.prompt_len:3d}, "
              f"prio {req.priority}] "
              f"-> {out[:8].tolist()}{'...' if len(out) > 8 else ''} "
              f"({gw.status(rid)}{swaps})")
    m = gw.metrics()
    print(f"{m['tokens']} tokens in {m['elapsed_s']:.2f} s "
          f"({m['tok_per_s']:.1f} tok/s); "
          f"p50 {m['p50_token_ms']:.1f} ms  p95 {m['p95_token_ms']:.1f} ms  "
          f"ttft {m['mean_ttft_ms']:.1f} ms")
    print(f"pages peak {m['kv_pages_peak']}  occupancy "
          f"{m['pool_occupancy_pct']:.1f}%  swap out/in "
          f"{m['swap_outs']}/{m['swap_ins']}  "
          f"preempted {m['preempted_requests']} "
          f"(ttft {m['preempted_ttft_ms']:.1f} ms)")
    print(f"prefill chunks {m['prefill_chunks']} "
          f"(occupancy {m['prefill_chunk_occupancy_pct']:.0f}%)  "
          f"sealed bytes/decode-token {m['sealed_bytes_per_token']:.0f}  "
          f"page closes {m['page_closes']} reopens {m['page_reopens']}")
    print(f"rotations {m['rotations']}  "
          f"launches verified: {m['launches_verified']}")
    if args.trace:
        n = gw.export_trace(args.trace, fmt="chrome")
        print(f"trace: {args.trace} ({n} events — load at "
              "https://ui.perfetto.dev)")
    if args.audit:
        n = gw.export_audit(args.audit, key_path=args.audit + ".key")
        report = gw.verify_audit()
        print(f"audit: {args.audit} ({n} records, key in "
              f"{args.audit}.key) — chain "
              f"{'OK' if report['ok'] else 'BROKEN: ' + str(report)}")


def _run_fixed(cfg, params, args) -> None:
    channel = (SecureChannel.establish() if args.security == "trusted"
               else SecureChannel.insecure())
    if args.security == "trusted":
        params = channel.upload_tree(params, donate=True)
    max_len = args.max_prompt + args.max_new + 4
    engine = ServeEngine(cfg=cfg, params=params, channel=channel,
                         max_len=max_len)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.slots, args.max_prompt), 0, cfg.vocab)}
    if cfg.frontend == "patch":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.slots, cfg.n_frontend_tokens, cfg.d_model))
    if cfg.frontend == "frame":
        batch["frame_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.slots, args.max_prompt, cfg.d_model))
    t0 = time.perf_counter()
    out = engine.generate(batch, n_new=args.max_new)
    dt = time.perf_counter() - t0
    print(out)
    print(f"{args.slots} x {args.max_new} tokens in {dt*1e3:.0f} ms "
          f"({args.slots*args.max_new/dt:.1f} tok/s); launches verified: "
          f"{channel.device_regs.last_nonce}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--engine", default="gateway",
                    choices=("gateway", "fixed"))
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages", type=int, default=64)
    ap.add_argument("--max-pages", type=int, default=4)
    ap.add_argument("--rotate-every", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="tokens per batched prefill chunk (multiple of "
                         "page-size; 0 = whole-prompt chunks)")
    ap.add_argument("--whole-page-reseal", action="store_true",
                    help="legacy baseline: reseal the whole tail page per "
                         "decode token instead of slice-sealed open pages")
    ap.add_argument("--hi-every", type=int, default=0,
                    help="every Nth request is high priority (0 = never)")
    ap.add_argument("--trace", default="",
                    help="record a trace and write it here as a "
                         "Perfetto-loadable Chrome trace_event file")
    ap.add_argument("--audit", default="",
                    help="export the hash-chained audit log (JSONL + "
                         "<path>.key verification key) here")
    ap.add_argument("--watch", type=int, default=0, metavar="N",
                    help="print the posture dashboard (SLOs, alerts, "
                         "per-tenant state) to stderr every N steps")
    ap.add_argument("--slo", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="monitor threshold override, e.g. "
                         "--slo ttft_p95_ms=250 (repeatable; see "
                         "repro.obs.MonitorConfig for field names)")
    ap.add_argument("--security", default="trusted", choices=("trusted", "off"))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    if args.engine == "gateway" and cfg.family == "dense":
        _run_gateway(cfg, params, args)
    else:
        if args.engine == "gateway":
            print(f"{cfg.family} family has no paged path yet; "
                  "falling back to the fixed-slot engine")
        _run_fixed(cfg, params, args)


if __name__ == "__main__":
    main()
