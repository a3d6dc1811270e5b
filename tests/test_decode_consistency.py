"""Decode path == full forward: run t decode steps from an empty cache and
compare the last-token logits to the full-sequence forward (fp32 params)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.transformer import build

CASES = ["llama3-8b", "minicpm3-4b", "xlstm-350m", "zamba2-7b", "grok-1-314b",
         "granite-4.0-h-micro"]


@pytest.mark.parametrize("arch", CASES)
def test_decode_matches_forward(arch):
    cfg = configs.get(arch).reduced(param_dtype="float32",
                                    compute_dtype="float32",
                                    capacity_factor=8.0)  # no MoE token drops
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    full_logits, _ = model.forward(params, {"tokens": toks})

    cache = model.init_cache(batch=B, max_seq=T)
    step = jax.jit(model.decode_step)
    for t in range(T):
        dec_logits, cache = step(params, {"tokens": toks[:, t:t + 1]}, cache,
                                 jnp.int32(t))
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)
