"""A committed cell cut to the registry's smoke widths, for CPU tests."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402


def small_cell(config: str, traffic: str, rate_rps: float,
               root: Path = spec.ROOT) -> spec.Cell:
    """The committed cell's metrics over one of the configuration and
    mix files under `root`, cut to the registry's smoke widths and a
    Poisson load of `rate_rps`, served by the family the file names."""
    cell = spec.load_cell("phi3-overload")
    base = spec.load_json(root / "bench" / "configs" / f"{config}.json")
    cfg = dict(base, hidden_size=256, num_attention_heads=4,
               intermediate_size=512, vocab_size=512, num_hidden_layers=2,
               serving=dict(base["serving"], max_batch=4),
               correct=dict(base["correct"], sample_requests=16))
    cfg["num_key_value_heads"] = 4 if cfg["num_key_value_heads"] > 1 else 1
    mix = dict(spec.load_traffic(traffic, root), kind="poisson",
               warmup_s=2.0, rate_rps=rate_rps)
    return dataclasses.replace(cell, name=f"{config}-{traffic}",
                               config_name=config, traffic_name=traffic,
                               config=cfg,
                               family=spec.family(cfg["family"], root),
                               traffic=mix)
