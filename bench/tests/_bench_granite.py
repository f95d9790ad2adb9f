"""A CPU-sized copy of the granite-4.0-h-small configuration for the
rehearsal tests: the real file with every width cut, one period of 10
layers, 2 of 8 experts held from expert 2 on, top-2, and the real muP
constants, NoPE and shared expert.

It computes in float32, with a limit of its own. In bfloat16 at these
widths a served token's gap reads up to 0.0016 and RoPE applied to the
NoPE layer 0.0016 to 0.0017, so no limit tells them apart. In float32
(CPU, seeds 2**33 + 7, 11, 13) the sound gap reads 0, the fp8 control
0.0063 to 0.0098, and the planted faults 0.0018 (RoPE) to 0.024 (shared
expert skipped)."""
from bench import harness


def tiny_granite() -> dict:
    c = harness.load_config("granite-4.0-h-small")
    return dict(c, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
                intermediate_size=32, shared_intermediate_size=64, num_local_experts=2,
                expert_first=2, num_experts_per_tok=2, vocab_size=512,
                published=dict(c["published"], num_local_experts=8),
                compute_dtype="float32", replica={"slots": 4, "max_len": 96},
                check=dict(c["check"], sample_requests=6, logit_gap_limit=0.0005))
