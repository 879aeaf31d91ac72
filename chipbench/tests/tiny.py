"""Every cell of BENCHMARK.json at a size a CPU test run holds: the same
files, with the traffic's (and the model's) scale cut, written to a
directory that the harness searches before its own."""
import json
import os

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRAFFIC = {
    "msd_rayleigh.energy": {"rows": {"n_nodes": [64, 64, 64],
                                     "energy_eps": [0.5, 1.0, 1.5]},
                            "steps": 120, "seeds": 8, "ref_block": 8},
    "msd_rayleigh.nsweep": {"rows": {"n_nodes": [16, 32, 64],
                                     "energy_eps": [0.5, 0.5, 0.5]},
                            "steps": 120, "seeds": 8, "ref_block": 8},
    "repro100m_ota.gbma": {"global_batch": 16, "seq_len": 32},
}
MODEL = {"repro100m_ota": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                           "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                           "vocab_size": 256, "logit_chunk": 16,
                           "attn_block_q": 16, "attn_block_kv": 16}}


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def catalog(tmp_dir: str, workloads=None) -> harness.Catalog:
    """A catalog whose cells run at test size: tiny traffic files, and
    tiny model configurations, under `tmp_dir`."""
    s = spec()
    real = harness.Catalog(ROOT, s)
    os.makedirs(os.path.join(tmp_dir, "traffic"), exist_ok=True)
    os.makedirs(os.path.join(tmp_dir, "configs"), exist_ok=True)
    for w in s["workloads"]:
        if workloads is not None and w["name"] not in workloads:
            continue
        t = real.traffic(w["config"], w["traffic"])
        t.update(TRAFFIC[w["name"]])
        with open(os.path.join(tmp_dir, "traffic",
                               f"{w['name']}.json"), "w") as f:
            json.dump(t, f)
    for c in s["configs"]:
        cfg = real.config(c["name"])
        if c["name"] in MODEL:
            cfg["model"].update(MODEL[c["name"]])
        path = os.path.join(tmp_dir, "configs", c["name"] + ".json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        c["file"] = path
    return harness.Catalog(ROOT, s, extra_dirs=[tmp_dir])


CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run(cat, workload, *, seed=2**31 + 5, seconds=0.5, trace=False):
    """One run of a cell on this process's first device, the look for a
    chip skipped."""
    import time

    import jax

    return harness.run_cell(catalog=cat, workload=workload, seed=seed,
                            seconds=seconds, trace=trace,
                            t0=time.perf_counter(),
                            devices=jax.devices()[:1], peak=CPU_PEAK)
