"""CPU-sized cells for the tests: the harness and a cell's own files, at the
published head size and grouping but few heads, few layers (two unless
asked), a small vocabulary and short sequences."""

from __future__ import annotations

import copy

from chipbench import harness, modelcfg

# cell -> (configuration, traffic)
FILES = {
    "internlm2_20b_8l.longctx": ("internlm2_20b_8l", "longctx"),
}


def smoke_cell(name: str, window_steps: int = 8, layers: int = 2) -> harness.Cell:
    config, traffic = FILES[name]
    cfg = modelcfg.load_json("configs", config)
    mix = modelcfg.load_json("traffic", traffic)
    limits = modelcfg.load_json("cells", name)
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    heads = 2 if group == 1 else group
    cfg.update(hidden_size=128 * heads, intermediate_size=4 * 128 * heads,
               num_hidden_layers=min(layers, cfg["num_hidden_layers"]), vocab_size=1024,
               num_attention_heads=heads, num_key_value_heads=heads // group)
    slots = 4
    cfg["engine"] = dict(cfg["engine"], batch_slots=slots, max_seq_len=1024)
    cfg["tierscape"] = dict(cfg["tierscape"], window_steps=window_steps)
    mix = copy.deepcopy(mix)
    mix.update(sessions=slots, contexts=[320, 384, 448])
    limits = dict(limits, sample_requests=3, min_compared_tokens=10, min_kv_pages=10)
    bench = harness.load_benchmark()
    return harness.Cell(name=name, config=cfg, mix=mix, limits=limits,
                        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"], chips=1)
