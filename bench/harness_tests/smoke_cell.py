"""A cell at a size the CPU runs in seconds: the qwen2-1.5b file with
its widths and depth cut, and the cell's mix with lengths cut to fit.
The scaled smoke cell's file adds linear RoPE scaling by 4, as
deepseek-coder-33b's does."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def smoke_config(scaled: bool = False) -> dict:
    cfg = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())
    if scaled:
        cfg["rope_scaling"] = {"type": "linear", "factor": 4.0}
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=512)
    cfg["serving"] = dict(cfg["serving"], slots=4, max_len=256, page=16,
                          prefill_chunk=64, decode_block=4)
    # the smoke limit lies between the program's smoke readings
    # (0.003-0.006) and the fp8 control's (0.040-0.067)
    cfg["check"] = dict(cfg["check"], min_tokens=20, gap_limit=0.02)
    return cfg


def smoke_mix(name: str) -> dict:
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    if mix["loop"] == "open":
        mix.update(session_rate_per_s=1.0, lead_s=4, drain_s=30)
        mix["shared_prefix"] = dict(
            mix["shared_prefix"],
            tokens={"dist": "uniform", "min": 48, "max": 96})
        mix["user_tokens"] = dict(mix["user_tokens"], median=16, min=4,
                                  max=48)
        mix["output_tokens"] = dict(mix["output_tokens"], median=6, min=2,
                                    max=12)
        mix["sessions"] = dict(mix["sessions"], think_s=dict(
            mix["sessions"]["think_s"], mean=1.0, min=0.2, max=3.0))
        mix["warm"] = dict(mix["warm"], chunk_rows=2)
    else:
        mix["prompt_tokens"] = dict(mix["prompt_tokens"], median=16, min=4,
                                    max=48)
        mix["output_tokens"] = dict(mix["output_tokens"], median=48, min=16,
                                    max=160)
    mix["warm"] = dict(mix["warm"], trace_s=0.5)
    return mix


def smoke_cell(traffic: str, scaled: bool = False) -> dict:
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"name": f"smoke.{traffic}", "chips": 1},
            "config": smoke_config(scaled), "mix": smoke_mix(traffic),
            "end_to_end": manifest["end_to_end"],
            "per_layer": manifest["per_layer"]}
