"""The benchmark's cells at smoke size, for the CPU tests.

Each cell keeps its mix's loop, its configuration's family and its
replicas (one per device), with the
program's smoke preset of the same architecture (float32, two layers,
width 64) and its widths written into the configuration the reference
reads.  At float32 a sound run's served tokens are the reference's argmax
exactly, so the gap limit here is float32 rounding.
"""
import copy
import json
import os
import subprocess
import sys
from typing import Optional

from bench import manifest

SMOKE_SHAPES = {
    "phi3-code": dict(arch="phi3-mini-3.8b", num_key_value_heads=4),
    "phi3-router-x4": dict(arch="phi3-mini-3.8b", num_key_value_heads=4),
}
SMOKE_LIMIT = 1e-4  # mean_logit_gap at float32: sound runs read 0.0


def smoke_cell(name: str, wide: bool = False) -> manifest.Cell:
    """``wide``: width 256 and 8,192 tokens of vocabulary instead of 64 and
    256, where the top logits lie close enough for int8 to reorder some."""
    cell = manifest.cell(name)
    s = SMOKE_SHAPES[name]
    d, ff, vocab = (256, 512, 8192) if wide else (64, 128, 256)
    cfg = copy.deepcopy(cell.config)
    cfg.update(hidden_size=d, intermediate_size=ff, num_attention_heads=4,
               num_key_value_heads=s["num_key_value_heads"],
               num_hidden_layers=2, vocab_size=vocab, torch_dtype="float32")
    overrides = {"attention_impl": "pallas"}
    if wide:
        overrides.update(d_model=d, d_ff=ff, vocab=vocab)
    cfg["program"] = {"arch": s["arch"], "preset": "smoke",
                      "config_overrides": overrides}
    cfg["serving"] = {"max_batch": 4, "max_len": 96, "prefill_len": 64}
    cfg["correct"] = {"mean_logit_gap": SMOKE_LIMIT, "sample_tokens": 30}
    mix = dict(cell.traffic, prompt_tokens=[8, 64], output_tokens=[4, 16],
               warmup={"requests": 2 * cell.chips, "output_tokens": 2})
    if mix["loop"] == "open":
        mix["rate_rps"] = 4.0
    cell.config, cell.traffic = cfg, mix
    return cell


def run_apart(name: str, seed: int, seconds: float, trace: bool = False,
              fault: Optional[str] = None, timeout: float = 300.0) -> dict:
    """The result object of one run (``bench/cell.py: run``) of the smoke
    cell in a process of its own, which has as many host CPU devices as
    the cell asks for chips, with ``fault`` (``bench/faults.py``) planted."""
    code = "\n".join([
        "import json, time",
        "from bench import cell as C",
        "from bench.faults import FAULTS",
        "from bench.smoke_cells import smoke_cell",
        f"if {fault!r}:",
        f"    setattr(*FAULTS[{fault!r}]())",
        f"r = C.run(smoke_cell({name!r}), {seed}, {seconds}, {trace}, "
        "time.time(), 'TPU v5 lite')",
        "print(json.dumps(r))"])
    chips = manifest.cell(name).chips
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=manifest.ROOT, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"{name} exited {p.returncode}:\n"
                           f"{p.stderr[-4000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])
