"""The benchmark's cells at a size a CPU test run can hold: the same
configuration (payload width, engine and service settings) and traffic
mix, with fewer records, smaller batches and shorter pools."""
from __future__ import annotations

import dataclasses

from bench import harness, loadgen

RECORDS = 4096
BATCH = 256
POOLS = {"POOL_BATCHES": 6, "SCAN_POOL_BATCHES": 3}


def tiny_cell(name: str, **mix_changes) -> harness.Cell:
    cell = harness.load_cell(name)
    cfg = dataclasses.replace(cell.config, records=RECORDS,
                              batch_txns=BATCH)
    changes = dict(pin_hold_s=0.4)
    changes.update(mix_changes)
    mix = dataclasses.replace(cell.mix, **changes)
    return dataclasses.replace(cell, config=cfg, mix=mix)


def small_pools(monkeypatch) -> None:
    """Draw the tiny pools (a ``pytest.MonkeyPatch``)."""
    for name, value in POOLS.items():
        monkeypatch.setattr(loadgen, name, value)
