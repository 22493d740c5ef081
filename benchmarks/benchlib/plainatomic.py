"""The plain reference of the atomic chain: what ``snowman-atomic``'s
chain adds up to, from the configuration and the seed alone.

Like ``plainref`` (whose Keccak, curve points, trie root, fee rule and
book it builds on) nothing here imports the program.  A pass's chain is
``value_tx``'s one transfer a block plus one atomic tx a block in the
ExtData (plugin/evm import_tx.go / export_tx.go, cited from memory):

- even heights: an ImportTx spends the next of the X-Chain's seeded AVAX
  UTXOs and credits ``(amount - fee) x 10^9`` wei to the next of the
  recipients, round-robin;
- odd heights: an ExportTx debits ``amount x 10^9`` wei from the
  exporter under its nonce (one more each time) and puts one UTXO of
  ``amount - fee`` nAVAX, owned by the importer, in the X-Chain's inbox.

The fee is the dynamic fee (tx.go CalculateDynamicFee) of the tx's gas
at the block's base fee: its signed bytes, a signature's cost and the
fixed cost of Apricot Phase 5, and for an export the configuration's
gas per EVM input and output.  ``ledger`` gives the account book;
``inbox`` the UTXOs shared memory must hold after a pass, keys and
bytes: for that every export is encoded and signed here (RFC 6979,
low s) as the configuration's codec says.
"""

from __future__ import annotations

import hashlib
import hmac
import struct
from typing import Dict, List, Tuple

from benchlib import plainref

N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
NANO = 10**9  # wei a nAVAX (tx.go x2cRate)
SIG_BYTES = 65


def sha256(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def _tag(what: str, seed: int, j: int) -> bytes:
    return sha256(f"snowman-atomic/{what}/{seed}/{j}".encode())


def recipients(config: dict, seed: int) -> List[bytes]:
    """The EVM addresses the imports credit, round-robin."""
    return [_tag("recipient", seed, j)[:20]
            for j in range(config["chain"]["recipients"])]


def utxo_tx_ids(config: dict, seed: int) -> List[bytes]:
    """The X-Chain tx ids of the seeded UTXOs (output 0 each), one an
    import."""
    return [_tag("utxo", seed, j) for j in range(imports(config))]


def imports(config: dict) -> int:
    return config["chain_blocks"] // 2


def keys(config: dict, seed: int) -> Dict[str, int]:
    """The chain's three keys: the transfer's sender (``value_tx``'s
    one key), the exporter, and the X-Chain key that owns the imported
    UTXOs and receives the exports."""
    first = config["chain"]["key_base"] + (seed << 12)
    return {"sender": first, "exporter": first + 1, "importer": first + 2}


def short_id(priv: int) -> bytes:
    """An X-Chain address: ripemd160(sha256(33-byte compressed key))."""
    x, y = plainref._mul(priv)
    h = hashlib.new("ripemd160")
    h.update(sha256(bytes([2 + (y & 1)]) + x.to_bytes(32, "big")))
    return h.digest()


# ------------------------------------------------------------ the codec
def _u16(v): return struct.pack(">H", v)
def _u32(v): return struct.pack(">I", v)
def _u64(v): return struct.pack(">Q", v)


def _ids(config: dict) -> dict:
    c = config["chain"]
    return {k: bytes.fromhex(c[k]) for k in
            ("c_chain_id", "x_chain_id", "avax_asset_id")}


def _output(config: dict, amount: int, owner: bytes) -> bytes:
    codec = config["chain"]["codec"]
    return (_ids(config)["avax_asset_id"] + _u32(codec["transfer_output"])
            + _u64(amount) + _u64(0) + _u32(1) + _u32(1) + owner)


def _export_unsigned(config: dict, address: bytes, amount: int,
                     nonce: int, out_amount: int, owner: bytes) -> bytes:
    c, ids = config["chain"], _ids(config)
    codec = c["codec"]
    return (_u16(codec["version"]) + _u32(codec["export_tx"])
            + _u32(c["network_id"]) + ids["c_chain_id"] + ids["x_chain_id"]
            + _u32(1) + address + _u64(amount) + ids["avax_asset_id"]
            + _u64(nonce)
            + _u32(1) + _output(config, out_amount, owner))


def _signed(config: dict, unsigned: bytes, sig: bytes) -> bytes:
    codec = config["chain"]["codec"]
    return unsigned + _u32(1) + _u32(codec["credential"]) + _u32(1) + sig


# Sizes of the two txs as signed, by the codec above: fixed whatever the
# values (every field has a fixed width)
IMPORT_BYTES = (2 + 4 + 4 + 32 + 32                 # header, chains
                + 4 + 32 + 4 + 32 + 4 + 8 + 4 + 4   # one input, one sig index
                + 4 + 20 + 8 + 32                   # one EVMOutput
                + 4 + 4 + 4 + SIG_BYTES)            # one credential
EXPORT_BYTES = (2 + 4 + 4 + 32 + 32
                + 4 + 20 + 8 + 32 + 8               # one EVMInput
                + 4 + 32 + 4 + 8 + 8 + 4 + 4 + 20   # one output, one owner
                + 4 + 4 + 4 + SIG_BYTES)


def fees(config: dict) -> Tuple[List[int], int, int]:
    """(base fee of blocks 1..n, import gas, export gas)."""
    g = config["chain"]["atomic_gas"]
    sig, fixed = g["per_signature"], g["fixed"]
    return (plainref.base_fees(config["chain_blocks"],
                               config["chain"]["block_gap_s"]),
            IMPORT_BYTES + sig + fixed,
            EXPORT_BYTES + g["per_evm_input"] + sig + g["per_output"]
            + fixed)


def dynamic_fee(gas: int, base_fee: int) -> int:
    """nAVAX: ceil(gas x base fee / 10^9)."""
    return (gas * base_fee + NANO - 1) // NANO


# ------------------------------------------------------------- signing
def _rfc6979_k(priv: int, digest: bytes) -> int:
    x = priv.to_bytes(32, "big")
    h1 = (int.from_bytes(digest, "big") % N).to_bytes(32, "big")
    v, k = b"\x01" * 32, b"\x00" * 32
    k = hmac.new(k, v + b"\x00" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        cand = int.from_bytes(v, "big")
        if 1 <= cand < N:
            return cand
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


_G_POWERS: List[tuple] = []  # 2^i G, i < 256


def _mul_g(k: int):
    """k G as a sum of the 2^i G a bit of k selects: one affine addition
    a set bit, no doubling (1,250 signatures a check)."""
    if not _G_POWERS:
        pt = plainref._G
        for _ in range(256):
            _G_POWERS.append(pt)
            pt = plainref._add(pt, pt)
    out = None
    for i in range(k.bit_length()):
        if k >> i & 1:
            out = plainref._add(out, _G_POWERS[i])
    return out


def sign(digest: bytes, priv: int) -> bytes:
    """65 bytes [R || S || V], low s, V the recovery id."""
    k = _rfc6979_k(priv, digest)
    rx, ry = _mul_g(k)
    r = rx % N
    s = pow(k, -1, N) * (int.from_bytes(digest, "big") + r * priv) % N
    v = (ry & 1) | (2 if rx >= N else 0)
    if s > N // 2:
        s, v = N - s, v ^ 1
    return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])


# ---------------------------------------------------------------- plan
def plan(config: dict, seed: int):
    """Per block (height 1..n): ("import", utxo index, recipient) or
    ("export", nonce)."""
    out, n_imp, n_exp = [], 0, 0
    rec = config["chain"]["recipients"]
    for height in range(1, config["chain_blocks"] + 1):
        if height % 2 == 0:
            out.append(("import", n_imp, n_imp % rec))
            n_imp += 1
        else:
            out.append(("export", n_exp))
            n_exp += 1
    return out


def ledger(config: dict, seed: int) -> plainref.Book:
    """Every account's nonce and balance after the chain: the transfer
    of each block, then its atomic tx."""
    c = config["chain"]
    k = keys(config, seed)
    sender, exporter = plainref.addresses(k["sender"], 2)
    book = plainref.Book({sender: c["root_funds"],
                          exporter: c["root_funds"]})
    base, import_gas, _ = fees(config)
    to = recipients(config, seed)
    for (op, *args), fee in zip(plan(config, seed), base):
        book.transfer(sender, b"\x00" * 20, c["value"], c["tx_gas"], fee)
        if op == "import":
            amount = c["import_nanoavax"] - dynamic_fee(import_gas, fee)
            addr = to[args[1]]
            book.balance[addr] = book.balance.get(addr, 0) + amount * NANO
            book.nonce.setdefault(addr, 0)
        else:
            if book.nonce[exporter] != args[0]:
                raise ValueError("the plan's export nonce is off")
            book.balance[exporter] -= c["export_nanoavax"] * NANO
            book.nonce[exporter] += 1
    return book


def inbox(config: dict, seed: int) -> Dict[str, Dict[bytes, bytes]]:
    """The UTXOs shared memory holds after a pass, key -> bytes:
    ``"x_to_c"`` what is left of the seeded UTXOs (every one an import
    spent: none) and ``"c_to_x"`` one UTXO an export, owned by the
    importer."""
    c = config["chain"]
    k = keys(config, seed)
    exporter = plainref.addresses(k["sender"], 2)[1]
    owner = short_id(k["importer"])
    base, _, export_gas = fees(config)
    codec = c["codec"]
    spent = {i for op, i, *_ in plan(config, seed) if op == "import"}
    left = {}
    for j, tx_id in enumerate(utxo_tx_ids(config, seed)):
        if j not in spent:
            left[sha256(tx_id + _u32(0))] = (
                _u16(codec["version"]) + tx_id + _u32(0)
                + _output(config, c["import_nanoavax"], owner))
    out = {}
    for (op, *args), fee in zip(plan(config, seed), base):
        if op != "export":
            continue
        amount = c["export_nanoavax"]
        put = amount - dynamic_fee(export_gas, fee)
        unsigned = _export_unsigned(config, exporter, amount, args[0], put,
                                    owner)
        tx_id = sha256(_signed(config, unsigned,
                               sign(sha256(unsigned), k["exporter"])))
        out[sha256(tx_id + _u32(0))] = (
            _u16(codec["version"]) + tx_id + _u32(0)
            + _output(config, put, owner))
    return {"x_to_c": left, "c_to_x": out}
