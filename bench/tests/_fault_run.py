"""Run one benchmark cell in-process with a fault planted under the
timed path; the run must then report "correct": false.

  python bench/tests/_fault_run.py <fault> <bench/run.py arguments>
"""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _half(choices, topk):
    """The second half of the batch is left out of routing and given
    what the routed half chose most often."""
    h = (len(choices) + 1) // 2
    choices = choices.copy()
    choices[h:] = np.bincount(choices[:h]).argmax()
    if topk is not None:
        topk = topk.copy()
        topk[h:] = topk[0]
    return choices, topk


def plant(fault: str):
    from repro.core import dispatch, state
    from repro.serving import engine
    D = dispatch.RouteDispatcher
    if fault == "state_unchanged":
        state.DoubleBuffer.commit = lambda self, g: self.front
    elif fault in ("half_batch", "answer_altered"):
        route_result, route = D.route_result, D.route

        def alter(ch, top, m):
            if fault == "half_batch":
                return _half(ch, top)
            ch = ch.copy()
            ch[0] = (ch[0] + 1) % m      # the first answer of each batch
            return ch, top

        def rr(self, st, q, b):
            return alter(*route_result(self, st, q, b), self.costs.shape[0])

        def rt(self, st, q, b):
            return alter(route(self, st, q, b), None, self.costs.shape[0])[0]

        D.route_result, D.route = rr, rt
    elif fault == "token_altered":
        generate = engine.FleetModel.generate

        def gen(self, tokens, max_new):
            out = generate(self, tokens, max_new)
            out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab
            return out

        engine.FleetModel.generate = gen
    elif fault == "no_exchange":
        from repro.kernels import similarity_topk as ST

        def merge_local(top_s, top_i, payloads, n, axis_name):
            # every shard keeps its own candidates: no all_gather
            pos = ST.jax.lax.top_k(top_s, n)[1]
            take = lambda x: ST.jnp.take_along_axis(
                x, pos.reshape(pos.shape + (1,) * (x.ndim - 2)), axis=1)
            return (take(top_s), take(top_i), tuple(take(p) for p in payloads))

        ST.shard_merge_topk = merge_local
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    from bench import run
    sys.exit(run.main(sys.argv[2:]))
