"""The one rate sweep of an open-loop cell, on the chip.

    python3 benchmarks/chip/sweep.py <cell> <seconds> <seed> <rate> [rate ...]

Builds the cell's gateway once, as run.py does, and offers its traffic at
each rate in turn: ``warm_s`` seconds to reach the steady state, then a
window of ``seconds``, then the gateway drains before the next rate.  Per
rate it prints one JSON line: requests due and finished in the window, the
backlog (requests submitted or due and not finished) at the window's start,
middle and end, the p50 and p90 of time to first token over the first and
the last third of the window's arrivals, the p95 gap between tokens, and
tokens per second.  A rate is sustained when its backlog does not grow
through the window (by at most ``GROWTH`` requests from start to end) and,
where both thirds hold arrivals, the last third's p90 is at most
``TAIL_RISE`` times the first's.  Rates run
in the order given; after two unsustained ones in a row the sweep stops.
The last line gives the knee, the highest rate sustained with every lower
one sustained too, and four fifths of it: the cell's rate.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
GROWTH = 2
TAIL_RISE = 2.0


def _backlog(drv, t: float) -> int:
    return sum(1 for r in drv.due if r.t_due <= t
               and (r.t_done is None or r.t_done > t))


def sustained(line: dict) -> bool:
    """The backlog does not grow, and the tail does not rise from the first
    third to the last (judged where both thirds hold arrivals: at a rate
    of a few requests a window, one may hold none)."""
    b0, _, b1 = line["backlog"]
    first, last = line["ttft_p90_ms"]
    rising = (first is not None and last is not None
              and last > TAIL_RISE * first)
    return b1 - b0 <= GROWTH and not rising


def main() -> None:
    cell_name, seconds, seed = sys.argv[1], float(sys.argv[2]), \
        int(sys.argv[3])
    rates = [float(r) for r in sys.argv[4:]]
    sys.path[:1] = [str(ROOT)]
    from benchmarks.chip import run
    cell = run.setup(cell_name)
    from repro.serve import SecureGateway

    from benchmarks.chip import load, spec, stats, traffic, weights
    cfg = spec.model_config(cell.config)
    g, mix, c = cell.gateway, cell.mix, cell.cell
    gw = SecureGateway(cfg, weights.generate(cfg, seed),
                       security=cell.security, max_slots=g["slots"],
                       page_size=g["page_size"], n_pages=g["n_pages"],
                       max_pages_per_seq=g["max_pages_per_seq"],
                       prefill_chunk=g["prefill_chunk"], donate_params=True)
    run.warm_programs(gw, cell, seed)
    run.warm_pool_shapes(gw, mix, g["page_size"])
    lines = []
    for rate in rates:
        stream = traffic.generate(mix, seed, cfg.vocab, traffic.stream_length(
            mix, c["warm_s"] + seconds + 30, rate, 0), rate)
        drv = load.Load(gw, stream, "open")
        t0 = time.perf_counter()
        drv.start(t0)
        w0 = t0 + c["warm_s"]
        w1 = w0 + seconds
        drv.run_until(w1)
        drv.close(w1)
        due = [r for r in drv.due if w0 <= r.t_due < w1]
        thirds = [[r for r in due if w0 + k * seconds / 3 <= r.t_due
                   < w0 + (k + 1) * seconds / 3] for k in (0, 2)]

        def ttft(recs, p):
            w = [(r.emits[0] if r.emits and r.emits[0] <= w1 else w1)
                 - r.t_due for r in recs]
            return stats.percentile(w, p) * 1e3 if w else None
        view = type("Run", (), {"window": (w0, w1), "due": drv.due})
        line = {
            "rate_per_s": rate, "due": len(due),
            "done": sum(r.t_done is not None and r.t_done <= w1 for r in due),
            "backlog": [_backlog(drv, t) for t in (w0, (w0 + w1) / 2, w1)],
            "ttft_p50_ms": [ttft(x, 0.5) for x in thirds],
            "ttft_p90_ms": [ttft(x, 0.9) for x in thirds],
            "itl_p95_ms": spec.metric_reader("itl_p95_ms")(view),
            "tok_per_s": spec.metric_reader("tok_per_s")(view),
        }
        line["sustained"] = sustained(line)
        print(json.dumps(line), flush=True)
        gw.drain()
        lines.append(line)
        if len(lines) >= 2 and not any(x["sustained"] for x in lines[-2:]):
            break
    knee = None
    for line in lines:
        if not line["sustained"]:
            break
        knee = line["rate_per_s"]
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None
                      else round(0.8 * knee, 4)}), flush=True)


if __name__ == "__main__":
    main()
