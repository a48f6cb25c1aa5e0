"""Paper Figure 3: encoder ablation. Four sentence-encoder stubs with the
fidelity ordering of the paper (mpnet ~ MiniLM > qwen3-0.6B > e5-large-
instruct) under the simulated online protocol (reduced stream to keep the
4x protocol affordable on this host)."""
from __future__ import annotations

from benchmarks.common import cached
from repro.core.policy import NeuralUCBRouter
from repro.core.protocol import run_protocol, summarize
from repro.core.utilitynet import UtilityNetConfig
from repro.data.encoders import ENCODERS
from repro.data.routerbench import RouterBenchSim, generate_routerbench


def _run(n_samples=14_000, n_slices=10, epochs=5):
    data = generate_routerbench(seed=0, n_samples=n_samples)
    out = {}
    for enc in ENCODERS:
        env = RouterBenchSim(seed=0, encoder=enc, n_slices=n_slices,
                             data=data)
        cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1],
                               num_actions=env.K, d_hidden=384, d_action=32)
        pols = {"neuralucb": NeuralUCBRouter(cfg, seed=0)}
        res = run_protocol(env, pols, epochs=epochs, verbose=False)
        summ = summarize(res)["neuralucb"]
        out[enc] = {
            "avg_reward": summ["avg_reward"],
            "final_cum_reward": summ["final_cum_reward"],
            "per_slice_reward": res["neuralucb"]["avg_reward"],
        }
        print(f"[encoders] {enc}: avg_reward={summ['avg_reward']:.4f}",
              flush=True)
    return out


def run(refresh: bool = False):
    out = cached("encoder_ablation", _run, refresh)
    rows = [("bench_encoders/encoder", "avg_reward", "final_cum_reward")]
    for enc, s in out.items():
        rows.append((f"fig3_{enc}", round(s["avg_reward"], 4),
                     round(s["final_cum_reward"], 1)))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit_csv
    from repro.common.compilation import enable_compile_cache

    enable_compile_cache()
    emit_csv(run())
