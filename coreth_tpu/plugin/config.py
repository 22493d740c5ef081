"""VM configuration.

Twin of reference plugin/evm/config.go (:82-230): the per-chain JSON
config AvalancheGo hands the VM at Initialize — API toggles, cache and
pool sizes, pruning/commit-interval knobs, gossip pacing — parsed with
defaults + deprecation warnings for renamed keys.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field, fields
from typing import List, Union

# old key -> new key (config.go Deprecate())
DEPRECATED_KEYS = {
    "corethAdminApiEnabled": "admin-api-enabled",
    "coreth-admin-api-enabled": "admin-api-enabled",
    "net-api-enabled": "eth-apis",
}


@dataclass
class Config:
    # API toggles
    eth_apis: List[str] = field(
        default_factory=lambda: ["eth", "eth-filter", "net", "web3"])
    admin_api_enabled: bool = False
    snowman_api_enabled: bool = False
    warp_api_enabled: bool = False
    # RPC limits (config.go rpc settings)
    rpc_gas_cap: int = 50_000_000
    rpc_tx_fee_cap: int = 100  # AVAX
    api_max_duration_ns: int = 0
    batch_request_limit: int = 40
    # caches / state
    trie_clean_cache_mb: int = 512
    snapshot_cache_mb: int = 256
    pruning_enabled: bool = True
    commit_interval: int = 4096
    state_sync_enabled: bool = False
    state_sync_min_blocks: int = 300_000
    # what executes a verified block: "host" — the Python twin of the
    # reference's StateProcessor on a StateDB, the default — or
    # "device": ReplayEngine behind Verify / Accept / Reject
    # (replay/device_processor.py).  The one switch; no environment
    # variable selects it
    state_processor: str = "host"
    # txpool
    tx_pool_price_limit: int = 1
    tx_pool_account_slots: int = 16
    tx_pool_global_slots: int = 5120
    tx_pool_account_queue: int = 64
    tx_pool_global_queue: int = 1024
    local_txs_enabled: bool = False
    # gossip / building
    min_block_build_interval_ms: int = 500
    push_gossip_num_validators: int = 100
    regossip_frequency_s: int = 60
    # profiling / observability
    metrics_expensive_enabled: bool = False
    continuous_profiler_dir: str = ""
    continuous_profiler_frequency_s: int = 900
    # offline pruning
    offline_pruning_enabled: bool = False
    offline_pruning_data_directory: str = ""

    warnings: List[str] = field(default_factory=list)


_KEYMAP = {
    "eth-apis": "eth_apis",
    "admin-api-enabled": "admin_api_enabled",
    "snowman-api-enabled": "snowman_api_enabled",
    "warp-api-enabled": "warp_api_enabled",
    "rpc-gas-cap": "rpc_gas_cap",
    "rpc-tx-fee-cap": "rpc_tx_fee_cap",
    "api-max-duration": "api_max_duration_ns",
    "batch-request-limit": "batch_request_limit",
    "trie-clean-cache": "trie_clean_cache_mb",
    "snapshot-cache": "snapshot_cache_mb",
    "pruning-enabled": "pruning_enabled",
    "commit-interval": "commit_interval",
    "state-sync-enabled": "state_sync_enabled",
    "state-sync-min-blocks": "state_sync_min_blocks",
    "state-processor": "state_processor",
    "tx-pool-price-limit": "tx_pool_price_limit",
    "tx-pool-account-slots": "tx_pool_account_slots",
    "tx-pool-global-slots": "tx_pool_global_slots",
    "tx-pool-account-queue": "tx_pool_account_queue",
    "tx-pool-global-queue": "tx_pool_global_queue",
    "local-txs-enabled": "local_txs_enabled",
    "min-block-build-interval": "min_block_build_interval_ms",
    "push-gossip-num-validators": "push_gossip_num_validators",
    "regossip-frequency": "regossip_frequency_s",
    "metrics-expensive-enabled": "metrics_expensive_enabled",
    "continuous-profiler-dir": "continuous_profiler_dir",
    "continuous-profiler-frequency": "continuous_profiler_frequency_s",
    "offline-pruning-enabled": "offline_pruning_enabled",
    "offline-pruning-data-directory": "offline_pruning_data_directory",
}


def parse_config(data: Union[bytes, str, dict, None]) -> Config:
    """Config bytes -> Config with defaults; unknown keys are recorded
    as warnings rather than rejected (config.go behavior), deprecated
    keys map onto their replacements."""
    cfg = Config()
    if not data:
        return cfg
    d = json.loads(data) if isinstance(data, (bytes, str)) else dict(data)
    for key, value in d.items():
        if key in DEPRECATED_KEYS:
            new = DEPRECATED_KEYS[key]
            cfg.warnings.append(
                f"deprecated key {key!r}; use {new!r}")
            key = new
        attr = _KEYMAP.get(key)
        if attr is None:
            cfg.warnings.append(f"unknown config key {key!r}")
            continue
        setattr(cfg, attr, value)
    return cfg
