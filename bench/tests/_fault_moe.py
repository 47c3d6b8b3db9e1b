"""Run one benchmark cell in-process with a fault planted in the expert
layer of the served model; the run must then report "correct": false.

  python bench/tests/_fault_moe.py <fault> <bench/run.py arguments>

expert_left_out: the first held expert's output is left out of every
token's sum (its router weight set to 0).
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def plant(fault: str):
    if fault != "expert_left_out":
        raise SystemExit(f"unknown fault {fault!r}")
    import jax.numpy as jnp
    from repro.models import moe
    held = moe._held_experts

    def without_first(params, xt, top_w, top_e, offset):
        return held(params, xt, jnp.where(top_e == offset, 0.0, top_w),
                    top_e, offset)

    moe._held_experts = without_first


if __name__ == "__main__":
    plant(sys.argv[1])
    from bench import run
    sys.exit(run.main(sys.argv[2:]))
