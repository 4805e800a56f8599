"""CLI sweep driver — shard ExperimentSpec grids across worker processes.

The figure benchmarks (:mod:`benchmarks.paper_figs`) run their sweep
cells sequentially inside one process; this driver externalizes the
grid instead: ``emit`` serializes a figure's cells (one JSON object per
cell, via :func:`paper_figs.specs_for_figure` — the specs are
round-trip safe by construction), ``run`` executes them one PROCESS per
cell (a crashed or OOM-killed cell loses only itself) and merges the
per-cell rows into one CSV, and ``cell`` is the internal child entry
point.  Because every cell is a plain spec JSON, grids can also be
hand-written or generated elsewhere — anything ``ExperimentSpec.
from_json`` accepts, including SystemSpec fault schedules.

    python -m benchmarks.sweep emit --figure fig1 --out grid.json
    python -m benchmarks.sweep run --specs grid.json --out sweep.csv \
        --jobs 4

``--in-process`` runs the cells in this process (no subprocess spawn) —
the test-suite path, and useful under a debugger.  A TPU chip belongs to
one process at a time, so ``--jobs > 1`` is refused where the cells
would run on a TPU.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import subprocess
import sys
import tempfile
from concurrent.futures import ThreadPoolExecutor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

CHECKPOINTS = (0.0, 0.25, 0.5, 0.75, 1.0)
FIELDS = ("config", "solver", "substrate", "iteration",
          "subspace_distance", "time_s", "time_axis_source")


def _figure_cells(figure: str, trial: int) -> list[dict]:
    from benchmarks.paper_figs import ALGORITHMS, specs_for_figure
    from repro.configs.paper import EXPERIMENT1_SMALL, EXPERIMENT2_SMALL
    configs = {"fig1": EXPERIMENT1_SMALL, "fig2": EXPERIMENT2_SMALL}[figure]
    specs = specs_for_figure(configs, trial=trial)
    # one key per (config, solver) cell, in specs_for_figure's order —
    # the same cfg.seed + trial derivation run_experiment_grid uses, so
    # the sharded sweep reproduces the in-process benchmark's cells
    keys = [cfg.seed + trial for cfg in configs for _ in ALGORITHMS]
    return [{"key": k, "spec": json.loads(s.to_json())}
            for k, s in zip(keys, specs)]


def run_cell(cell: dict) -> list[dict]:
    """Execute one sweep cell in THIS process and return its CSV rows."""
    from repro.api import ExperimentSpec, run_experiment
    spec = ExperimentSpec.from_json(json.dumps(cell["spec"]))
    trace = run_experiment(spec, key=int(cell.get("key", 0)))
    rows = []
    n = len(trace.sd_max)
    for frac in CHECKPOINTS:
        i = min(int(frac * (n - 1)), n - 1)
        rows.append({
            "config": spec.name or spec.solver.name,
            "solver": spec.solver.name,
            "substrate": spec.substrate,
            "iteration": i,
            "subspace_distance": float(trace.sd_max[i]),
            "time_s": float(trace.time_axis[i]),
            "time_axis_source": trace.time_axis_source,
        })
    return rows


def _run_cell_subprocess(cell: dict) -> list[dict]:
    """Execute one cell in a CHILD process (crash isolation) and parse
    the row JSON it prints on its last stdout line."""
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False,
                                     dir=None) as f:
        json.dump(cell, f)
        path = f.name
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = (os.path.join(REPO_ROOT, "src")
                             + os.pathsep + env.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.sweep", "cell",
             "--spec", path],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sweep cell failed (rc={proc.returncode}):\n"
                f"{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])
    finally:
        os.unlink(path)


def cmd_emit(args) -> None:
    cells = _figure_cells(args.figure, args.trial)
    with open(args.out, "w") as f:
        json.dump(cells, f, indent=1)
    print(f"wrote {len(cells)} cells to {args.out}")


def _child_platform() -> str:
    """JAX's default backend as a cell child would see it — asked of a
    short-lived child, so this parent never holds a chip."""
    proc = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"platform probe failed:\n{proc.stderr[-2000:]}")
    return proc.stdout.strip().splitlines()[-1]


def cmd_run(args) -> None:
    with open(args.specs) as f:
        cells = json.load(f)
    if not args.in_process and args.jobs > 1 and _child_platform() == "tpu":
        raise SystemExit("benchmarks.sweep: --jobs > 1 would start several "
                         "processes on one TPU host, and a chip belongs to "
                         "one process at a time; use --jobs 1")
    worker = run_cell if args.in_process else _run_cell_subprocess
    if args.in_process or args.jobs <= 1:
        results = [worker(c) for c in cells]
    else:
        with ThreadPoolExecutor(max_workers=args.jobs) as pool:
            results = list(pool.map(worker, cells))
    rows = [row for cell_rows in results for row in cell_rows]
    with open(args.out, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=FIELDS)
        w.writeheader()
        w.writerows(rows)
    print(f"{len(cells)} cells -> {len(rows)} rows -> {args.out}")


def cmd_cell(args) -> None:
    from repro.utils.compile_cache import use_persistent_cache
    use_persistent_cache()
    with open(args.spec) as f:
        cell = json.load(f)
    print(json.dumps(run_cell(cell)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.sweep",
                                 description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("emit", help="serialize a figure's sweep grid")
    p.add_argument("--figure", choices=("fig1", "fig2"), required=True)
    p.add_argument("--trial", type=int, default=0)
    p.add_argument("--out", required=True)
    p.set_defaults(fn=cmd_emit)

    p = sub.add_parser("run", help="execute a grid, one process per cell")
    p.add_argument("--specs", required=True, help="JSON grid from emit")
    p.add_argument("--out", required=True, help="merged CSV path")
    p.add_argument("--jobs", type=int, default=2,
                   help="cells run at once (CPU only: >1 is refused on TPU)")
    p.add_argument("--in-process", action="store_true",
                   help="run cells in this process (tests / debugging)")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("cell", help="internal: run one cell, print rows")
    p.add_argument("--spec", required=True, help="single-cell JSON file")
    p.set_defaults(fn=cmd_cell)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
