"""Paper reproduction example: DQN on CartPole with every replay sampler.

Trains four agents (uniform / PER / AMPER-k / AMPER-fr) for --steps scan
iterations and prints train/test scores — Fig. 8(c) + Table 1 at laptop
scale.  With --num-envs N each iteration steps N environments in lockstep
and writes N transitions into the replay ring in one batched scatter, so
frames = steps * num_envs.

Run:  PYTHONPATH=src python examples/dqn_cartpole.py --steps 6000
      PYTHONPATH=src python examples/dqn_cartpole.py --num-envs 16
"""
import argparse
import time

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.rl.dqn import DQNConfig, make_dqn
from repro.rl.envs import available_envs

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=6000)
ap.add_argument("--env", default="cartpole", choices=available_envs())
ap.add_argument("--agent", default="dqn",
                choices=("dqn", "double", "dueling", "double-dueling"),
                help="agent variant (Q-head x target rule)")
ap.add_argument("--n-step", type=int, default=1,
                help="n-step return horizon")
ap.add_argument("--num-envs", type=int, default=1,
                help="parallel environments per iteration")
ap.add_argument("--replay", type=int, default=2000)
ap.add_argument("--seed", type=int, default=0)
args = ap.parse_args()
enable_compile_cache()

frames = args.steps * args.num_envs
print(f"agent={args.agent} n_step={args.n_step}")
print(f"{'sampler':14s} {'train(last64)':>14s} {'test(10ep)':>11s} "
      f"{'sec':>6s} {'frames/s':>9s}")
for sampler in ("uniform", "per-sumtree", "amper-k", "amper-fr"):
    cfg = DQNConfig(env=args.env, sampler=sampler, agent=args.agent,
                    n_step=args.n_step, replay_size=args.replay,
                    num_envs=args.num_envs,
                    eps_decay_steps=args.steps // 2, learn_start=200)
    dqn = make_dqn(cfg)
    key = jax.random.key(args.seed)
    # AOT-compile so trace/compile cost stays out of the frames/s column
    train_c = dqn.train.lower(key, args.steps).compile()
    t0 = time.perf_counter()
    state, metrics = train_c(key)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    test = float(dqn.evaluate(state, jax.random.key(args.seed + 100), 10))
    print(f"{sampler:14s} {float(metrics['return_mean'][-1]):14.1f} "
          f"{test:11.1f} {dt:6.1f} {frames / dt:9.0f}")
