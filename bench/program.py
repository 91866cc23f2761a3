"""The system under test, built from a configuration file: the one place
where the benchmark constructs the program's objects."""
from __future__ import annotations


def make_pipeline(cfg: dict, key):
    """The configuration's ``FeaturePipeline``: stored or regenerated
    CWS parameters, int32 indices or packed words."""
    from repro.pipeline import FeaturePipeline, FeatureSpec
    if cfg["b_t"] != 0:
        raise ValueError("the reference is the 0-bit scheme: b_t must be 0")
    spec = FeatureSpec(num_hashes=cfg["num_hashes"], b_i=cfg["b_i"],
                       b_t=cfg["b_t"], packed=bool(cfg.get("packed")))
    make = (FeaturePipeline.create_regen if cfg["params"] == "regen"
            else FeaturePipeline.create)
    return make(key, cfg["dim"], spec)
