"""Device time per round of an LM's frozen prefix forward inside the round
(a stage past 0 with the cache declined): the union of the ops traced
under ``jax.named_scope("prefix")`` in
``core/freezing.stage_prefix_features`` (the embedding and the frozen
layers; ``bench/scopes.py``), averaged over the chips, over the rounds
traced."""
from bench import scopes


def read(ctx):
    return scopes.scoped_device_ms(ctx, "prefix")
