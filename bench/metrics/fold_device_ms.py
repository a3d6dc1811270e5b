"""Device time per round of the Eq. 1 fold: the union of the ops traced
under ``jax.named_scope("fold")`` in ``make_fused_round``
(``bench/scopes.py``), averaged over the chips, over the rounds traced."""
from bench import scopes


def read(ctx):
    return scopes.scoped_device_ms(ctx, "fold")
