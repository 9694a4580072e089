"""§Perf hillclimb driver: for each of the three selected cells, lower the
variant ladder (plus 0-layer/1-period probes for corrected accounting) and
store JSONs under benchmarks/results/hillclimb/.

Cells (selection criteria per the brief):
  gemma3_27b   train_4k   — most representative of the paper's technique
                            (over-decomposition/microbatch + overlap)
  pixtral_12b  decode_32k — most collective-bound baseline (cache all-gather)
  mamba2_370m  train_4k   — worst roofline fraction (no TP mapping)
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "benchmarks", "results", "hillclimb")

PLAN = [
    ("gemma3-27b", "train_4k",
     ["od2", "od4", "dots", "dots_sp", "dots_sp_od4", "sp", "sp_od4",
      "sp_od8"]),
    ("pixtral-12b", "decode_32k", ["kvseq_model"]),
    ("mamba2-370m", "train_4k",
     ["dots", "ssd_chunk128", "ssd_chunk128_dots_sp"]),
    # breadth: the seq-sharded-KV decode fix applied to every
    # kv-head-replicated architecture (beyond-paper optimized column)
    ("yi-9b", "decode_32k", ["kvseq_model"]),
    ("phi4-mini-3.8b", "decode_32k", ["kvseq_model"]),
    ("llama4-scout-17b-a16e", "decode_32k", ["kvseq_model"]),
    ("whisper-large-v3", "decode_32k", ["kvseq_model"]),
]


def run(arch, shape, variant, probe=None, timeout=3600):
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs import canon
    tag = f"{canon(arch)}__{shape}__{variant}"
    if probe is not None:
        tag += f"__probe{probe}"
    out_path = os.path.join(OUT, tag + ".json")
    if os.path.exists(out_path):
        try:
            if "error" not in json.load(open(out_path)):
                print(f"SKIP {tag}", flush=True)
                return
        except Exception:
            pass
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--variant", variant, "--out", out_path]
    if probe is not None:
        cmd += ["--probe", str(probe)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"         # dry-run lowering on virtual devices
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)
    if proc.returncode != 0:
        with open(out_path, "w") as f:
            json.dump({"arch": arch, "shape": shape, "variant": variant,
                       "probe": probe, "error": proc.stderr[-3000:]}, f)
        print(f"FAIL {tag} ({time.time()-t0:.0f}s)", flush=True)
    else:
        print(f"OK   {tag} ({time.time()-t0:.0f}s)", flush=True)


def main():
    os.makedirs(OUT, exist_ok=True)
    for arch, shape, variants in PLAN:
        for v in variants:
            run(arch, shape, v)
            run(arch, shape, v, probe=0)
            run(arch, shape, v, probe=1)
    print("hillclimb sweep done", flush=True)


if __name__ == "__main__":
    main()
