"""Plain references, one module per kind of deployment. A configuration
names its reference (``"reference": "kv_plain"``); the module's
``Reference`` class is built as ``Reference(n_shards, n_keys, value_bytes)``.
"""

from __future__ import annotations

import importlib


def load_reference(config: dict):
    return importlib.import_module(
        f"chipbench.reference.{config['reference']}"
    ).Reference
