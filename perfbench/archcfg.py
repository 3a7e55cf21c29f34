"""A configuration file, as the program's model config.

A file under ``configs/`` holds the model's published keys as run (the
reduced ones at their reduced values), and two blocks for the program:
``arch_keys`` says which published key gives each size of the program's
``ArchConfig``, and ``arch`` holds what the program needs beyond those
(the layer pattern, the dtype).  So every size comes from the published
key, in one place.
"""
from __future__ import annotations


def arch_fields(conf: dict) -> dict:
    """The ``ArchConfig`` keyword arguments of a configuration file."""
    fields = dict(conf["arch"])
    for field, key in conf["arch_keys"].items():
        fields[field] = conf[key]
    return fields


def arch_config(conf: dict):
    from repro.models.config import ArchConfig, LayerSpec

    fields = arch_fields(conf)
    fields["pattern"] = tuple(LayerSpec(**p) for p in fields["pattern"])
    return ArchConfig(**fields)
