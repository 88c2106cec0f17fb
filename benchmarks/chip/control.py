"""Readings that set a cell's ``max_logit_gap`` limit, on the chip.

    python3 benchmarks/chip/control.py <cell> <seconds> <seed> [seed ...]

For each seed, in one process: a run of the cell as run.py makes it, with a
window of ``seconds`` at the cell's own load, then the check on its sample
of finished requests three ways, all against the float32 reference: the
served tokens (the program's reading), and the tokens that the reference
computed in int8 and in fp8 puts first at the same positions (the
control's readings, check.gaps(quant=...)).  One JSON line per seed.

The limit lies above the largest program reading over a dozen seeds or
more and below the smallest control reading; PERF.md keeps the readings.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> None:
    cell_name, seconds = sys.argv[1], float(sys.argv[2])
    seeds = [int(s) for s in sys.argv[3:]]
    sys.path[:1] = [str(ROOT)]
    from benchmarks.chip import run
    cell = run.setup(cell_name)
    from benchmarks.chip import check
    for seed in seeds:
        t0 = time.perf_counter()
        served = run.serve(cell, seed, seconds, trace=False, t_process=t0)
        res = run.judge(served)
        line = {"cell": cell_name, "seed": seed,
                "program": res["checks"]["max_logit_gap"]["value"],
                "checked_tokens": res["checks"]["checked_tokens"]["value"],
                "failed": res["checks"]["failed_requests"]["value"],
                "setup_s": res["metrics"]["setup_s"]["value"]}
        for quant in ("int8", "fp8"):
            g = check.gaps(cell.config, served.cfg, seed, served.picked,
                           **check.shapes(cell.mix), quant=quant)
            line[quant] = max(float(x.max()) for x in g)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
