"""Beyond-quickstart comparison: progressive SmartFreeze stages vs vanilla
full-model training on the same token budget — shows the FLOPs saving
(Eq. 5) at matched loss trajectory.

Run:  PYTHONPATH=src python examples/progressive_vs_vanilla.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax, jax.numpy as jnp
from repro import configs
from repro.core import freezing
from repro.core.memory_model import full_model_flops, stage_flops
from repro.data.synthetic import make_lm_batch
from repro.launch.cache import use_compile_cache
from repro.models.transformer import build
from repro.optim import adamw


def main():
    use_compile_cache()
    cfg = configs.get("llama3-8b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in make_lm_batch(cfg, 4, 64).items()}

    for label, stage in [("vanilla (full model)", None),
                         ("SmartFreeze stage 0", 0),
                         ("SmartFreeze stage 1", 1)]:
        plan = freezing.make_stage_plan(cfg, stage)
        frozen, active = freezing.init_stage_active(model, params, plan,
                                                    jax.random.PRNGKey(1))
        opt = adamw(3e-3)
        step = jax.jit(freezing.make_train_step(model, plan, opt, remat=False))
        st = freezing.TrainState(active, frozen, opt.init(active), jnp.int32(0))
        for _ in range(6):
            st, m = step(st, batch)
        fl = (full_model_flops(cfg, 4, 64) if stage is None
              else stage_flops(cfg, stage, 4, 64)["total"])
        print(f"{label:24s} loss={float(m['loss']):.4f}  step FLOPs={fl:.3e}")


if __name__ == "__main__":
    main()
