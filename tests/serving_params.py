"""What serving tests share: a `ServingEngine` (and each router) CONSUMES
the parameter tree it is given (`serving.split_layer_stacks` deletes every
stacked layer leaf as it splits it), so a test that goes on using its
stacked tree (parity against `generate`, a second engine) hands over a copy."""

import jax
import jax.numpy as jnp


def own(params):
    """A copy of `params` for an engine to consume."""
    return jax.tree.map(jnp.copy, params)
