"""Genesis JSON parsing — the plugin's wire format for chain creation.

Twin of reference core/genesis.go UnmarshalJSON + plugin/evm/vm.go:448
(the VM receives genesis bytes from AvalancheGo and decodes them into a
chain config + allocation).  Accepts the geth-style layout:

    {"config": {"chainId": 43111, "apricotPhase1BlockTimestamp": 0, ...},
     "alloc": {"<hex addr>": {"balance": "0x..", "code": "0x..",
                              "nonce": "0x..", "storage": {...}}},
     "gasLimit": "0x7a1200", "timestamp": "0x0", ...}

Unknown config keys are ignored; missing fork keys default to None
(fork inactive), matching the reference's pointer-nil semantics.
"""

from __future__ import annotations

import json
from typing import Optional, Union

from coreth_tpu.chain import Genesis, GenesisAccount
from coreth_tpu.params import ChainConfig

# JSON key -> ChainConfig field.  Block-number forks use geth names;
# Avalanche forks use the network-upgrade timestamp names
# (params/config.go:419-470).
CONFIG_KEYS = {
    "chainId": "chain_id",
    "homesteadBlock": "homestead_block",
    "eip150Block": "eip150_block",
    "eip155Block": "eip155_block",
    "eip158Block": "eip158_block",
    "byzantiumBlock": "byzantium_block",
    "constantinopleBlock": "constantinople_block",
    "petersburgBlock": "petersburg_block",
    "istanbulBlock": "istanbul_block",
    "muirGlacierBlock": "muir_glacier_block",
    "apricotPhase1BlockTimestamp": "apricot_phase1_time",
    "apricotPhase2BlockTimestamp": "apricot_phase2_time",
    "apricotPhase3BlockTimestamp": "apricot_phase3_time",
    "apricotPhase4BlockTimestamp": "apricot_phase4_time",
    "apricotPhase5BlockTimestamp": "apricot_phase5_time",
    "apricotPhasePre6BlockTimestamp": "apricot_phase_pre6_time",
    "apricotPhase6BlockTimestamp": "apricot_phase6_time",
    "apricotPhasePost6BlockTimestamp": "apricot_phase_post6_time",
    "banffBlockTimestamp": "banff_time",
    "cortinaBlockTimestamp": "cortina_time",
    "durangoBlockTimestamp": "durango_time",
    "cancunTime": "cancun_time",
}


def _num(v, default: int = 0) -> int:
    if v is None:
        return default
    if isinstance(v, str):
        return int(v, 16) if v.startswith("0x") else int(v)
    return int(v)


def _opt_num(v) -> Optional[int]:
    return None if v is None else _num(v)


def _hexb(v: str) -> bytes:
    return bytes.fromhex(v[2:] if v.startswith("0x") else v)


def parse_chain_config(d: dict) -> ChainConfig:
    kwargs = {}
    for json_key, field in CONFIG_KEYS.items():
        if json_key in d:
            v = d[json_key]
            kwargs[field] = _num(v) if field == "chain_id" else _opt_num(v)
    cfg = ChainConfig()
    for field, value in kwargs.items():
        setattr(cfg, field, value)
    return cfg


def parse_genesis_json(data: Union[bytes, str, dict]) -> Genesis:
    if isinstance(data, (bytes, str)):
        d = json.loads(data)
    else:
        d = data
    config = parse_chain_config(d.get("config", {}))
    alloc = {}
    for addr_hex, acct in d.get("alloc", {}).items():
        addr = _hexb(addr_hex)
        if len(addr) != 20:
            raise ValueError(f"bad alloc address {addr_hex!r}")
        storage = {_hexb(k).rjust(32, b"\x00"):
                   _hexb(v).rjust(32, b"\x00")
                   for k, v in acct.get("storage", {}).items()}
        alloc[addr] = GenesisAccount(
            balance=_num(acct.get("balance")),
            code=_hexb(acct["code"]) if acct.get("code") else b"",
            nonce=_num(acct.get("nonce")),
            storage=storage)
    return Genesis(
        config=config,
        alloc=alloc,
        nonce=_num(d.get("nonce")),
        timestamp=_num(d.get("timestamp")),
        extra_data=_hexb(d["extraData"]) if d.get("extraData") else b"",
        gas_limit=_num(d.get("gasLimit")),
        difficulty=_num(d.get("difficulty")),
        coinbase=_hexb(d["coinbase"]) if d.get("coinbase")
        else b"\x00" * 20,
        base_fee=_opt_num(d.get("baseFeePerGas")),
    )
