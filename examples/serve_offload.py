"""End-to-end tiered serving — the paper's experiment (§VII) on a model
ladder: two reduced-width LM variants as the "ED tier" (MobileNet-alpha
analogue) and the full model as the "ES tier" (ResNet50 analogue), with
REAL measured latencies and REAL per-job top-1 next-token accuracy.

Reproduces the shape of the paper's Figs 3-6:
  * job assignment vs T (Fig 3),
  * total accuracy: AMR^2 vs LP bound vs Greedy-RRA vs dual (Figs 4/5),
  * predicted vs wall-clock makespan + violation (Fig 6),
plus the fault-tolerance story: an ES outage period (replanned onto the ED
ladder) and a straggler period (profile re-measured).

    PYTHONPATH=src python examples/serve_offload.py [--periods 6] [--n 24]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_edge import CONFIG as ES_CFG, ED_VARIANTS
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.steps import make_train_step
from repro.models import forward, init_params, logits_from_h
from repro.optim import adamw_init
from repro.api import solve
from repro.core.types import enable_compile_cache
from repro.serving import ServingRuntime, TierProfile, measure_latency


def build_models(seed: int = 0, train_steps: int = 30):
    """Train the ladder briefly on the synthetic stream so accuracy is
    ordered by capacity (a_1 <= a_2 <= a_es), like Table I."""
    import dataclasses
    models = []
    for i, cfg in enumerate(list(ED_VARIANTS) + [ES_CFG]):
        cfg = dataclasses.replace(cfg, attn_impl="dense")
        key = jax.random.key(seed + i)
        params = init_params(cfg, key)
        step = jax.jit(make_train_step(cfg, lr=3e-3))
        opt = adamw_init(params)
        pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=64, global_batch=8,
                                        seed=seed))
        # more steps for bigger models -> ordered accuracies
        for s in range(train_steps * (i + 1)):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}
            params, opt, _ = step(params, opt, batch)
        models.append((cfg, params))
    return models


def make_apply(cfg, params):
    @jax.jit
    def fwd(tokens):
        h = forward(params, {"tokens": tokens}, cfg)
        logits = logits_from_h(params, h, cfg)
        pred = jnp.argmax(logits[:, :-1], -1)
        return (pred == tokens[:, 1:]).mean(axis=1)  # per-job top-1

    def apply(jobs):
        # bucket batch to the next power of two: stable jit shapes across
        # plan periods (otherwise every distinct group size recompiles)
        toks = jnp.stack([jnp.asarray(j) for j in jobs])
        n = toks.shape[0]
        bucket = 1 << (n - 1).bit_length()
        toks = jnp.pad(toks, ((0, bucket - n), (0, 0)))
        acc = fwd(toks)[:n]
        return [float(x) for x in acc]
    return apply


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--periods", type=int, default=6)
    ap.add_argument("--train-steps", type=int, default=30)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    print("== training the model ladder (ED x2 + ES) ==")
    models = build_models(train_steps=args.train_steps)
    applies = [make_apply(c, p) for c, p in models]

    # measured test accuracy per model (Table I analogue)
    pipe = TokenPipeline(DataConfig(vocab_size=ES_CFG.vocab_size, seq_len=64,
                                    global_batch=16, seed=99))
    test_jobs = [pipe.batch_at(0)["tokens"][i] for i in range(16)]
    accs = [float(np.mean(app(test_jobs))) for app in applies]
    print(f"ladder accuracies (a_1..a_m, a_es): {[round(a,3) for a in accs]}")

    # measured per-job latency (Table II analogue): single size class
    lats = [measure_latency(lambda b=app: b(test_jobs[:1]), (),
                            iters=args.iters) for app in applies]
    comm = 0.2 * lats[-1]          # payload upload ~ fraction of ES compute
    print(f"ladder latencies (s/job): {[round(l,4) for l in lats]}, "
          f"comm {comm:.4f}")

    profile = TierProfile(
        name="lm-ladder",
        p_ed=np.array([[lats[0], lats[1]]]),
        p_es=np.array([lats[2] + comm]),
        acc=np.array(accs), classes=[64])

    # a T sweep: job assignment (Fig 3) + accuracy vs policies (Fig 4)
    n = args.n
    base_T = n * lats[1]
    print(f"\n== T sweep (n={n}) ==")
    print(f"{'T':>8} {'policy':>7} {'A_pred':>7} {'A_LP':>7} "
          f"{'A_greedy':>8} {'A_dual':>7}  jobs/model")
    for tf in (0.3, 0.6, 1.0, 1.6):
        T = base_T * tf
        inst = profile.instance(np.full(n, 64), T)
        p = solve(inst, policy="amr2")
        g = solve(inst, policy="greedy")
        d = solve(inst, policy="dual")
        print(f"{T:8.3f} {p.solver:>7} {p.accuracy:7.2f} "
              f"{float(p.lp_accuracy or 0):7.2f} "
              f"{g.accuracy:8.2f} "
              f"{d.accuracy:7.2f}  "
              f"{p.to_schedule().counts().tolist()}")

    # the serving loop with failures + stragglers (Fig 6 + fault story)
    print(f"\n== period-T serving loop ==")
    rt = ServingRuntime(profile, applies[:2], applies[2],
                        T=base_T * 0.8, policy="auto")
    rng = np.random.default_rng(0)
    for period in range(args.periods):
        jobs = [pipe.batch_at(100 + period)["tokens"][i] for i in range(n)]
        es_fail = period == 2
        if period == 4:
            # inject a straggler: wrap ED applies with a delay
            slow = [lambda js, a=a: (time.sleep(0.05 * len(js)), a(js))[1]
                    for a in applies[:2]]
            rt.apply_ed = slow
        stats = rt.run_period(jobs, np.full(n, 64), es_fail=es_fail)
        print(f"period {period}: policy={stats.policy} "
              f"A={stats.total_accuracy:.2f} pred={stats.predicted_makespan:.3f}s "
              f"wall={stats.wall_makespan:.3f}s viol={100*stats.violation:.0f}% "
              f"plan={1e3*stats.plan_seconds:.1f}ms "
              f"{'ES-FAIL->replanned ' if stats.replanned else ''}"
              f"{'profile-updated' if stats.profile_updated else ''}")
    print("done.")


if __name__ == "__main__":
    main()
