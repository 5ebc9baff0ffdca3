"""The program's DeepFM measure, built on the benchmark's weights."""
from __future__ import annotations


def program_measure(params: dict, m: dict):
    from repro.core.measures import deepfm_measure
    from repro.models.deepfm import DeepFMConfig
    cfg = DeepFMConfig(fm_dim=m["fm_dim"], deep_dim=m["deep_dim"],
                       mlp_hidden=tuple(m["hidden"]))
    return deepfm_measure({"mlp": params}, cfg)
