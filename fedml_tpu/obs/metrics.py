"""Metrics with pluggable sinks.

TPU-native replacement for ``core/mlops`` (SURVEY.md §2.12/§5): the reference
ships metrics over MQTT to a SaaS backend (``MLOpsMetrics``); here the same
call shapes write to pluggable sinks — stdout, JSONL file, or an in-memory
buffer (tests).  Spans are ``obs/trace.py``'s.
"""

from __future__ import annotations

import json
import logging
import sys
import time
from typing import Optional

log = logging.getLogger("fedml_tpu")


class MetricsLogger:
    """``mlops.log(...)`` equivalent (``core/mlops/__init__.py:172``)."""

    def __init__(self, jsonl_path: Optional[str] = None, stdout: bool = True):
        self.jsonl_path = jsonl_path
        self.stdout = stdout
        self.records: list[dict] = []
        self._fh = open(jsonl_path, "a") if jsonl_path else None

    def log(self, metrics: dict, step: Optional[int] = None) -> None:
        rec = {k: (float(v) if hasattr(v, "__float__") else v) for k, v in metrics.items()}
        if step is not None:
            rec["step"] = step
        rec["ts"] = time.time()
        self.records.append(rec)
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        if self.stdout:
            items = " ".join(
                f"{k}={v:.5g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in rec.items() if k != "ts"
            )
            log.info("metrics %s", items)

    def close(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None
