"""Paper Figure 3: run the online protocol once per text encoder and
print the comparison (``--samples 36497`` for the full stream).

    PYTHONPATH=src python examples/encoder_ablation.py [--samples 5000]
"""
import argparse

from repro.core.policy import NeuralUCBRouter
from repro.core.protocol import run_protocol, summarize
from repro.core.utilitynet import UtilityNetConfig
from repro.data.encoders import ENCODERS
from repro.data.routerbench import RouterBenchSim, generate_routerbench


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=5000)
    ap.add_argument("--slices", type=int, default=4)
    args = ap.parse_args()

    data = generate_routerbench(seed=0, n_samples=args.samples)
    rows = []
    for enc in ENCODERS:
        env = RouterBenchSim(seed=0, encoder=enc, n_slices=args.slices,
                             data=data)
        cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
        res = run_protocol(env, {"nucb": NeuralUCBRouter(cfg, seed=0)},
                           epochs=3, verbose=False)
        s = summarize(res)["nucb"]
        rows.append((enc, s["avg_reward"]))
        print(f"{enc:35s} avg_reward={s['avg_reward']:.4f}")
    best = max(rows, key=lambda r: r[1])
    print(f"\nbest encoder: {best[0]} ({best[1]:.4f}) — expected ordering: "
          "mpnet ~ MiniLM > Qwen3-0.6B > e5-large-instruct (paper Fig. 3)")


if __name__ == "__main__":
    main()
