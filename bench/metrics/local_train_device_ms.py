"""Device time per round of the clients' local steps: the union of the ops
traced under ``jax.named_scope("local_train")`` in ``make_fused_round``
(``bench/scopes.py``), averaged over the chips, over the rounds traced."""
from bench import scopes


def read(ctx):
    return scopes.scoped_device_ms(ctx, "local_train")
