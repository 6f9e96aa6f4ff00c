"""Runs perfbench/stages_run.py on the CPU at a small size (the
rehearsal's 64 MiB workspace, 1024-deep links, 16K-deep tcaches, a
64-lane batch), with the cell's per-layer readers run beside its
end-to-end ones (an untraced run reports only end-to-end metrics):

    python tests/perfbench/stages_rehearsal.py WORKLOAD

Prints the run's result line last, as run.py does; the `window:`
stderr line carries stages_run.py's decomposition.
"""
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def small(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["wksp_size"] = 1 << 26
    for ln in cfg["links"]:
        ln["depth"] = 1024 if ln["mtu"] <= 1280 else min(ln["depth"], 256)
    for tc in cfg["tcaches"]:
        tc["depth"] = 1 << 14
    for t in cfg["tiles"]:
        if t["name"] == cfg["device_tile"]:
            t["args"]["batch"] = 64
    return cfg


def main(workload: str) -> int:
    from perfbench import harness, stages_run
    harness.PLATFORM = "cpu"
    harness.WARM_DRAIN_S = 3.0
    harness.DRAIN_S = 60.0      # ends once every valid txn came
    run_cell = harness.run_cell

    def small_cell(w, seed, seconds, trace, t0, rate=None):
        return run_cell(w, seed, seconds, trace, t0, overrides={
            "config": small,
            "cell": lambda c: dict(c, rate=120, warmup_s=0.5),
            "metrics": lambda m: {"end_to_end": m["end_to_end"]
                                  + m["per_layer"], "per_layer": []}})
    harness.run_cell = small_cell
    return stages_run.main(["--workload", workload, "--seed",
                            str(2 ** 40 + 13), "--seconds", "2",
                            "--trace", "0"])


if __name__ == "__mp_main__":
    from perfbench.run import tile_process_hook
    tile_process_hook()

if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
