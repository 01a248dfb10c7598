"""A benchmark cell cut to a size the CPU tests can run."""

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: every configuration in bench/configs, by name
CONFIGS = sorted(os.path.basename(p)[:-len(".json")] for p in
                 glob.glob(os.path.join(ROOT, "bench", "configs", "*.json")))


def config_arch(config: str) -> tuple[dict, object]:
    """Configuration ``config`` as run, and its architecture module."""
    from bench import run

    m = run.read_json(os.path.join(ROOT, "bench", "configs",
                                   config + ".json"))
    return m, run.load_arch(m["arch"])


def tiny_model(config: str) -> dict:
    """Configuration ``config`` at its architecture's CPU size."""
    m, arch = config_arch(config)
    return arch.tiny(m)


def tiny_cell(name: str = "qwen2-1.5b.b4s1024", root: str = ROOT) -> dict:
    """Cell ``name`` of BENCHMARK.json under ``root`` at its
    architecture's tiny widths (``tiny``), with half its rows (one at
    least) of a 64th of its length (B4 x S1024 -> 2 x 16, B1 x S4096 ->
    1 x 64), with its own limits."""
    from bench import run

    cell = run.load_cell(name, root)
    cell["model"] = cell["arch"].tiny(cell["model"])
    tr = cell["traffic_mix"]
    cell["traffic_mix"] = dict(tr, batch=max(1, tr["batch"] // 2),
                               seq=tr["seq"] // 64)
    return cell
