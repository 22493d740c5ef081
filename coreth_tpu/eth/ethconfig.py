"""Engine-side configuration defaults.

Twin of reference eth/ethconfig/config.go: the knobs eth/backend.go
consumes — cache sizing, tx-pool limits, gas-price oracle bounds,
pruning/commit-interval policy — with the same defaults where they
transfer to this architecture.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class TxPoolDefaults:
    """core/txpool DefaultConfig mirror."""
    price_limit: int = 1
    account_slots: int = 16
    global_slots: int = 4096 + 1024
    account_queue: int = 64
    global_queue: int = 1024


@dataclass
class GPODefaults:
    """eth/gasprice Default oracle knobs."""
    blocks: int = 40
    percentile: int = 60


@dataclass
class EthConfig:
    """ethconfig.Config (the Defaults value)."""
    network_id: int = 1
    pruning: bool = True               # false = archive mode
    commit_interval: int = 4096
    snapshot_cache: int = 256          # MB-shaped knob; snapshots on if > 0
    freezer_dir: Optional[str] = None
    freeze_threshold: int = 90_000
    bloom_section_size: Optional[int] = None
    keystore_dir: Optional[str] = None
    allow_unfinalized_queries: bool = False
    rpc_gas_cap: int = 50_000_000
    # "host": core's Processor on a StateDB (the default); "device":
    # ReplayEngine behind insert / accept / reject
    # (replay/device_processor.py)
    state_processor: str = "host"
    tx_pool: TxPoolDefaults = field(default_factory=TxPoolDefaults)
    gpo: GPODefaults = field(default_factory=GPODefaults)


DEFAULTS = EthConfig()
