"""Architecture families: everything of the benchmark that knows a model's
shape, one module per family, named by the ``reference`` key of a
configuration file (the same name finds its plain float32 reference under
``bench/reference/``).

A family module gives, for a configuration file's dict ``c``:

* ``sizes(c)``: the sizes its other functions use;
* ``make_weights(c, seed)``: seeded random weights, made on the device in
  one jitted call, in the layout its reference reads;
* ``program_tree(w)``: the same arrays nested as the served program's
  ``params``, without copies;
* ``program_config(c)``: the program's ``ModelConfig``, every size, dtype
  and constant taken from ``c``;
* ``prefill_work(c, n)`` and ``decode_work(c, positions)``: the (FLOPs,
  bytes) the algorithm needs for one prompt of ``n`` tokens and for one
  decode step over active slots at ``positions`` (rules in
  ``bench/counts.py``);
* ``model_keys(c)``: the keys of ``c`` that its reference's
  ``gap_program`` is given, as a dict of hashable values.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np


def load_family(name: str):
    return importlib.import_module(f"bench.families.{name}")


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole-number seed (64 bits and more)."""
    state = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jnp.asarray(state, dtype=jnp.uint32)
