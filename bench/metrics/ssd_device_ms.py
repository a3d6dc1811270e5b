"""Device time per round of the Mamba-2 SSD scan: the union of the ops
traced under ``jax.named_scope("ssd")`` in ``models/ssm.py``
(``_ssd_chunked``, frozen and trained layers alike; ``bench/scopes.py``),
averaged over the chips, over the rounds traced."""
from bench import scopes


def read(ctx):
    return scopes.scoped_device_ms(ctx, "ssd")
