"""granite-4.0-h-small's block at a small size on the CPU: one period of 10
layers (nine Mamba-2, one NoPE GQA at index 5), d_model 64, 8 routed experts
top-2 with a shared expert, and the four muP multipliers, on seeded random
weights from the benchmark's Granite family.

* A replica's prefill and decode give the logits of the float32 reference
  (``bench/reference/granite.py``: the sequential Mamba-2 recurrence, not
  the chunked scan) within a tolerance its fp8 control exceeds.
* Dispatch is dropless: a token's decode logits do not depend on what the
  other slots hold.
* The expert shares of a layer, the shared expert counted once, add up to
  the uncut layer of the reference.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.families import granite as fam
from bench.reference import granite as ref
from repro.models.layers.basic import apply_ffn
from repro.models.layers.moe import apply_moe
from repro.runtime.serve_engine import Replica

SEED = 2**33 + 17
#: Relative logit error (over the largest logit) a float32 program may show
#: against the float32 reference: the chunked scan, the program's order of
#: sums and its top-k renormalisation round differently from the sequential
#: recurrence, by 1.1e-6 to 1.8e-6 here. The fp8 control reads 0.38 to 0.44,
#: RoPE applied to this NoPE block 0.15.
TOL = 1e-4


def small_file(held: int = 2, first: int = 2) -> dict:
    c = harness.load_config("granite-4.0-h-small")
    return dict(c, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
                intermediate_size=32, shared_intermediate_size=64,
                num_local_experts=held, expert_first=first, num_experts_per_tok=2,
                vocab_size=512, published=dict(c["published"], num_local_experts=8))


def program(c: dict, w: dict, **extra):
    cfg = dataclasses.replace(fam.program_config(c), compute_dtype="float32", **extra)
    return cfg, fam.program_tree(w)


def reference_logits(c: dict, w: dict, tokens: np.ndarray, *, fp8: bool = False):
    keys = fam.model_keys(c)
    hidden = ref.hidden_states(keys, w, jnp.asarray(tokens), fp8=fp8)
    return ref.logits_at(keys, w, hidden, jnp.arange(len(tokens)), fp8=fp8)


def relative(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_replica_prefill_and_decode_match_the_reference():
    """Three slots, prompts of 5, 13 and 20 tokens (none a whole number of
    8-token chunks), then 6 decode steps of given tokens: every logit row
    the replica returns is the reference's row at that position."""
    c = small_file()
    w = fam.make_weights(c, SEED)
    cfg, params = program(c, w)
    replica = Replica("r", cfg, params, slots=3, max_len=32)
    rng = np.random.default_rng(0)
    lengths, steps = (5, 13, 20), 6
    seqs = [rng.integers(1, c["vocab_size"], size=n + steps, dtype=np.int32)
            for n in lengths]
    got = [[replica.prefill(slot, seq[:n])] for slot, (seq, n) in
           enumerate(zip(seqs, lengths))]
    for step in range(steps):
        tokens = np.array([seq[n + step] for seq, n in zip(seqs, lengths)], np.int32)
        positions = np.array([n + step for n in lengths], np.int32)
        logits = replica.decode(tokens, positions)
        for slot in range(3):
            got[slot].append(logits[slot])
    for seq, n, rows in zip(seqs, lengths, got):
        want = reference_logits(c, w, seq)[n - 1: n + steps]
        assert relative(jnp.stack(rows), want) < TOL
        control = reference_logits(c, w, seq, fp8=True)[n - 1: n + steps]
        assert relative(control, want) > 100 * TOL


def test_a_tokens_decode_does_not_depend_on_the_other_slots():
    """Slot 15 of 16 decodes the same token after the same prompt: alone,
    beside 15 copies of itself (every pair routed to the same two experts,
    twice the 8 a capacity of 1.25 k T / E would keep), and beside other
    prompts. Its logits are the same each time."""
    c = small_file(held=8, first=0)
    cfg, params = program(c, fam.make_weights(c, SEED))
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, c["vocab_size"], size=12, dtype=np.int32)
    others = [rng.integers(1, c["vocab_size"], size=12, dtype=np.int32)
              for _ in range(15)]
    rows = []
    for fill in ([], [prompt] * 15, others):
        replica = Replica("r", cfg, params, slots=16, max_len=16)
        tokens = np.zeros((16,), np.int32)
        positions = np.zeros((16,), np.int32)
        for slot, p in enumerate(fill + [prompt]):
            slot = 15 if slot == len(fill) else slot
            replica.prefill(slot, p)
            tokens[slot], positions[slot] = 7, len(p)
        rows.append(replica.decode(tokens, positions)[15])
    assert relative(rows[1], rows[0]) < 1e-6
    assert relative(rows[2], rows[0]) < 1e-6


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of an 8-expert layer, 2 experts each: the routed
    parts of the four add up, with the shared expert once, to the
    reference's uncut layer."""
    c = small_file(held=8, first=0)
    w = fam.make_weights(c, SEED)
    cfg, tree = program(c, w)
    lw = {k: v[0] for k, v in w["layers"][0].items()}
    layer = jax.tree.map(lambda a: a[0], tree["blocks"]["pos0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (24, c["hidden_size"]), jnp.float32)
    want = ref.experts(fam.model_keys(c), lw, x, False)
    shares = []
    for first in range(0, 8, 2):
        share = dict(layer, **{k: layer[k][first:first + 2]
                               for k in ("w_gate", "w_up", "w_down")})
        share_cfg = dataclasses.replace(cfg, moe_held=2, moe_first=first)
        shares.append(apply_moe(share_cfg, share, x)[0])
    shared = apply_ffn(cfg, layer["shared"], x)
    assert relative(sum(shares) - 3 * shared, want) < 1e-5
    # Each share alone is a part, not the whole.
    assert all(relative(s, want) > 1e-2 for s in shares)


def test_slot_merge_carries_every_hybrid_cache_leaf_at_published_widths():
    """The replica merges a batch-1 prefill cache into slot ``s`` with
    ``big.at[:, s].set(one[:, 0])`` on every leaf: at the cut's published
    widths (32 slots x 4096), every leaf of the hybrid cache, the Mamba-2
    ``conv`` window and ``ssm`` state beside the K/V, has its slot on axis 1
    and the same shape otherwise."""
    from repro.models import Model

    c = harness.load_config("granite-4.0-h-small")
    model = Model(fam.program_config(c))
    slots, max_len = c["replica"]["slots"], c["replica"]["max_len"]
    big = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    one = jax.eval_shape(lambda: model.init_cache(1, max_len))
    leaves = jax.tree_util.tree_leaves_with_path(big)
    names = {str(path[-1].key) for path, _ in leaves}
    assert names == {"k", "v", "conv", "ssm"}
    for (path, b), o in zip(leaves, jax.tree.leaves(one)):
        assert b.shape[1] == slots and o.shape[1] == 1, path
        assert b.shape[:1] + b.shape[2:] == o.shape[:1] + o.shape[2:], path
    merged = jax.eval_shape(
        lambda b, o: jax.tree.map(lambda x, y: x.at[:, 3].set(y[:, 0]), b, o), big, one)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), merged) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), big)


def test_name_scopes_reach_the_compiled_programs():
    """Every mechanism's ``jax.named_scope`` is in the op names of the
    compiled decode and prefill (the name stacks of the device trace)."""
    import re

    from repro.models import Model

    c = small_file()
    cfg, params = program(c, jax.eval_shape(lambda: fam.make_weights(c, 1)))
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(4, 32))
    tokens = jax.ShapeDtypeStruct((4,), jnp.int32)
    prompt = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def prefill_b1(params, tokens):
        return model.prefill(params, {"tokens": tokens}, model.init_cache(1, 32))

    for name, fn, args in (("decode", model.decode, (params, cache, tokens, tokens)),
                           ("prefill_b1", prefill_b1, (params, prompt))):
        text = jax.jit(fn).lower(*args).compile().as_text()
        stacks = set(re.findall(r'op_name="([^"]*)"', text))
        scopes = ["mamba", "attn", "moe", "route", "experts", "shared"]
        if name == "prefill_b1":
            scopes.append("ssd")
        for scope in scopes:
            assert any(s.startswith(f"jit({name})/") and scope in s.split("/")[1:-1]
                       for s in stacks), (name, scope)
