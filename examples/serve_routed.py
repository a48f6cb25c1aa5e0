"""End-to-end driver: serve batched requests through the FULL stack —
NeuralUCB router in front of the PHYSICAL arm pool (DESIGN.md §16):
each arm a real `ModelConfig`, cost/latency derived from its decode
roofline on tpu-v5e, quality from the RouterBench tables via the
explicit arm mapping. The small arm (mamba2-130m) executes REAL jitted
prefill+decode on CPU; the large arms are roofline-clocked. Bandit
feedback closes the loop, Algorithm-1 style slices.

    PYTHONPATH=src python examples/serve_routed.py [--waves 6 --wave-size 16]
"""
import argparse

import numpy as np

from repro.armpool import build_arm_engines, build_pool_env
from repro.core.policy import NeuralUCBRouter
from repro.core.utilitynet import UtilityNetConfig
from repro.experiments import ArmPoolSpec, DataSpec
from repro.serving import Request, RoutedServingPool

# dense / SSM / MoE / hybrid-frontier — one arm per architecture class
POOL_ARMS = ("mamba2_130m", "llama3_2_3b", "qwen3_moe_30b_a3b",
             "jamba_1_5_large_398b")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=6)
    ap.add_argument("--wave-size", type=int, default=16)
    ap.add_argument("--train-every", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=4)
    args = ap.parse_args()

    aspec = ArmPoolSpec(arms=POOL_ARMS, hardware="tpu-v5e",
                        decode_batch=8, context=2048,
                        max_new=args.max_new)
    env, pool = build_pool_env(aspec, DataSpec(n_samples=2000, n_slices=4))
    engines, info = build_arm_engines(pool, aspec, max_batch=8)

    print(f"physical pool on {pool.hardware} "
          f"(real decode: {info['real_decode_arms']}):")
    for a in range(pool.K):
        print(f"  {pool.arms[a]:<22} {pool.params_b[a]:8.1f}B "
              f"{int(pool.chips[a]):3d} chip(s) "
              f"{pool.usd_per_token[a]:.2e} $/tok  "
              f"quality<-{pool.rb_models[a]}")

    ucfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1],
                            num_actions=pool.K)
    router = NeuralUCBRouter(ucfg, seed=0, batch_size=64)
    # cost = the pool's roofline $/token; quality = the mapped replay
    # columns already compiled into env's tables
    serving = RoutedServingPool(router, engines, pool.usd_per_token,
                                quality_table=env.data["quality"],
                                max_batch=8)

    rng = np.random.default_rng(0)
    for wave in range(args.waves):
        idx = rng.integers(0, env.n, size=args.wave_size)
        reqs = [Request(tokens=rng.integers(1, 200,
                                            size=int(rng.integers(4, 12))),
                        x_emb=env.x_emb[i], x_feat=env.data["x_feat"][i],
                        domain=int(env.data["domain"][i]), sample_idx=int(i))
                for i in idx]
        out = serving.submit(reqs)
        rewards = [o["reward"] for o in out]
        actions = [o["action"] for o in out]
        print(f"wave {wave + 1}: mean_reward={np.mean(rewards):.3f} "
              f"action_mix={np.bincount(actions, minlength=pool.K)} "
              f"tokens[0]={out[0]['tokens'][:5]}")
        if (wave + 1) % args.train_every == 0:
            metrics = serving.end_slice(epochs=2)
            print(f"  [slice end] trained: "
                  f"{ {k: round(v, 4) for k, v in metrics.items()} }")
    real = {e.name: e.decode_steps for e in engines if e.real_decode}
    print(f"served {len(serving.log)} requests total; "
          f"avg reward {np.mean([r['reward'] for r in serving.log]):.3f}; "
          f"real decode steps {real}")


if __name__ == "__main__":
    main()
