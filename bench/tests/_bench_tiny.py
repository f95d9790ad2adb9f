"""A CPU-sized copy of a benchmark cell for the rehearsal tests: the real
configuration and mix files with every width, depth and length cut, run
through the harness's own functions (the command itself refuses a CPU)."""
import jax

from bench import harness, traffic

#: Peaks for a CPU "device", so that the traced run's readers can be run.
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
PER_LAYER = [(m["name"], m["unit"]) for m in harness.load_benchmark()["per_layer"]]


def tiny_config(name: str = "smollm-135m") -> dict:
    c = harness.load_config(name)
    return dict(c, num_hidden_layers=4, hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=256, vocab_size=512,
                replica={"slots": 4, "max_len": 96},
                check=dict(c["check"], sample_requests=6))


def tiny_mix(name: str = "azure-conv") -> dict:
    mix = traffic.load_mix(name)
    mix = dict(mix, prompt=dict(mix["prompt"], median=12, min=4, max=32, quantum=8),
               output=dict(mix["output"], median=12, min=4, max=32), ramp_s=0.5)
    if mix["loop"] == "open":
        mix["rate_rps"] = 10.0
    else:
        mix["requests_per_client"] = 50
    return mix


def run(seed: int = 2**33 + 1, *, c=None, mix=None, trace=False, control=False,
        seconds=1.5, devices=None):
    return harness.run_cell(c or tiny_config(), mix or tiny_mix(), seed=seed,
                            seconds=seconds, trace=trace,
                            devices=devices or jax.devices()[:1],
                            per_layer=PER_LAYER, control=control, peak=CPU_PEAK,
                            log=lambda line: None)
