"""Paper Figure 4: NeuralUCB vs the max-quality reference — inference cost
and selected quality. Reads the full-protocol artifact from bench_rewards
(runs it if missing) and derives the Fig. 4 comparison."""
from __future__ import annotations

from benchmarks import bench_rewards
from benchmarks.common import cached


def run(refresh: bool = False):
    bench_rewards.run(refresh=refresh)  # ensure artifact exists
    out = cached("rewards_full", lambda: (_ for _ in ()).throw(
        RuntimeError("rewards artifact missing")))
    mq = out["max_quality_reference"]
    nucb = out["summary"]["neuralucb"]
    rows = [("bench_cost_quality/metric", "neuralucb", "max_quality_ref",
             "ratio")]
    rows.append(("avg_cost", round(nucb["avg_cost"], 5),
                 round(mq["avg_cost"], 5),
                 round(nucb["avg_cost"] / mq["avg_cost"], 4)))
    rows.append(("avg_quality", round(nucb["avg_quality"], 4),
                 round(mq["avg_quality"], 4),
                 round(nucb["avg_quality"] / mq["avg_quality"], 4)))
    rows.append(("avg_reward", round(nucb["avg_reward"], 4),
                 round(mq["avg_reward"], 4),
                 round(nucb["avg_reward"] / mq["avg_reward"], 4)))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit_csv
    from repro.common.compilation import enable_compile_cache

    enable_compile_cache()
    emit_csv(run())
