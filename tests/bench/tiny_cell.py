"""A benchmark cell cut to a size the CPU tests can run."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: widths of the CPU-size twin of a configuration
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            head_dim=16, vocab_size=256, num_hidden_layers=2)


def tiny_model(config: str) -> dict:
    from bench import run

    m = run.read_json(os.path.join(ROOT, "bench", "configs",
                                   config + ".json"))
    kv = 2 if m["num_key_value_heads"] < m["num_attention_heads"] else 4
    return dict(m, num_key_value_heads=kv, **TINY)


def tiny_cell(name: str = "qwen2-1.5b.b4s1024") -> dict:
    """Cell ``name`` of BENCHMARK.json at tiny widths, batch 2 x 16,
    with its own limits."""
    from bench import run

    cell = run.load_cell(name)
    cell["model"] = tiny_model(cell["config"])
    cell["traffic_mix"] = dict(cell["traffic_mix"], batch=2, seq=16)
    return cell
