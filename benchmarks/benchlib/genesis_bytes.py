"""The genesis BYTES AvalancheGo would hand the VM for a ``Genesis`` a
chain builder returns: the inverse of the program's
``plugin/genesis_json.parse_genesis_json`` (the same genesis block comes
back out of them), for a pass that goes through ``VM.initialize``.  The
program has no use for it; the benchmark and its tests do."""

import json


def genesis_to_json(genesis) -> str:
    """Refuses what the wire format cannot say."""
    from coreth_tpu.plugin.genesis_json import CONFIG_KEYS
    if genesis.config.precompile_upgrades or any(
            a.mc_balance for a in genesis.alloc.values()) \
            or genesis.number or genesis.gas_used \
            or genesis.parent_hash != b"\x00" * 32:
        raise ValueError("genesis does not fit the JSON layout")
    config = {key: getattr(genesis.config, field)
              for key, field in CONFIG_KEYS.items()
              if getattr(genesis.config, field) is not None}
    alloc = {}
    for addr, a in genesis.alloc.items():
        acct = {"balance": hex(a.balance)}
        if a.code:
            acct["code"] = "0x" + a.code.hex()
        if a.nonce:
            acct["nonce"] = hex(a.nonce)
        if a.storage:
            acct["storage"] = {"0x" + k.hex(): "0x" + v.hex()
                               for k, v in a.storage.items()}
        alloc[addr.hex()] = acct
    d = {"config": config, "alloc": alloc, "nonce": hex(genesis.nonce),
         "timestamp": hex(genesis.timestamp),
         "gasLimit": hex(genesis.gas_limit),
         "difficulty": hex(genesis.difficulty),
         "coinbase": "0x" + genesis.coinbase.hex()}
    if genesis.extra_data:
        d["extraData"] = "0x" + genesis.extra_data.hex()
    if genesis.base_fee is not None:
        d["baseFeePerGas"] = hex(genesis.base_fee)
    return json.dumps(d)
