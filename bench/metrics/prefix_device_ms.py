"""Device time per round of the frozen prefix's forward inside the round
(a stage past 0 with the cache declined): the union of the ops traced
under ``jax.named_scope("prefix")`` in
``core/freezing_cnn.cnn_prefix_features`` (``bench/scopes.py``), averaged
over the chips, over the rounds traced."""
from bench import scopes


def read(ctx):
    return scopes.scoped_device_ms(ctx, "prefix")
