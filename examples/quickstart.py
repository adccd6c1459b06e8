"""Quickstart tour: model -> train step -> prefill/decode -> offload plan.

Runs in ~1 min on CPU:
    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import paper_instance
from repro.core.types import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import decode_step, init_params, prefill
from repro.api import solve
from repro.optim import adamw_init


def main():
    enable_compile_cache()
    # 1. a reduced internlm2-family model (same code path as the 20B)
    cfg = get_smoke_config("internlm2_20b")
    key = jax.random.key(0)
    params = init_params(cfg, key)
    print(f"model: {cfg.name}  params={cfg.param_count():,} (analytic, "
          f"full config would be {cfg.param_count():,})")

    # 2. a couple of train steps
    step = jax.jit(make_train_step(cfg, lr=1e-2))
    opt = adamw_init(params)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}
    for i in range(3):
        params, opt, loss = step(params, opt, batch)
        print(f"train step {i}: loss {float(loss):.4f}")

    # 3. prefill + a few decode steps
    cache, logits = prefill(params, {"tokens": batch["tokens"][:, :24]},
                            cfg, max_seq=32)
    toks = jnp.argmax(logits, -1)
    for _ in range(4):
        logits, cache = decode_step(params, toks, cache, cfg)
        toks = jnp.argmax(logits, -1)
    print(f"decoded to index {int(cache['index'])}")

    # 4. the paper: plan a batch of 30 inference jobs under a 2 s budget
    inst = paper_instance(30, T=2.0, seed=0)
    sol = solve(inst)                   # registry front door, policy="auto"
    print(f"offload plan [{sol.solver}]: {sol.to_schedule().summary()}")
    print(f"jobs per model: {sol.to_schedule().counts()}  "
          f"(last = offloaded to ES tier)")


if __name__ == "__main__":
    main()
