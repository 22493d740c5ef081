"""The plain reference of the CONTRACT: ``snowman.Block`` as AvalancheGo
states it, with nothing of the program in it.

``plainref`` is the reference of the arithmetic (what a chain of
transfers adds up to).  This is the reference of what a VM owes
consensus when blocks are verified, accepted and rejected one at a time
and a verified block is not yet an accepted one:

- ``verify(b)``: ``b``'s parent has been verified and is accepted or
  still processing on a branch consensus can still accept (no ancestor
  rejected, the last accepted block among its ancestors).  ``b``
  becomes PROCESSING.  Its state is its parent's with its own
  operations added; nobody else's state moves.  Verifying a decided
  block again changes nothing.
- ``accept(b)``: ``b`` is processing and its parent is the last accepted
  block.  ``b`` becomes ACCEPTED and the last accepted block; the
  accepted state is ``b``'s.
- ``reject(b)``: ``b`` is processing.  ``b`` becomes REJECTED and
  leaves no trace in any other block's state.  (Consensus rejects a
  block once a conflicting one is accepted, ancestors first; a
  processing child of a rejected block can only be rejected.)

A block is (id, parent id, height) and a function that adds its
operations to a book; the book of a processing block is a deep copy of
its parent's with that function applied, so any book will do
(``plainref.Book``, ``plainevm``'s).  For any script of calls this gives
every block's status, the last accepted id and the accepted book (and
through it the accepted state root).
"""

from __future__ import annotations

import copy
from typing import Callable, Dict, List, Optional, Tuple

UNKNOWN = "unknown"
PROCESSING = "processing"
ACCEPTED = "accepted"
REJECTED = "rejected"


class ContractError(Exception):
    """The call is one the contract does not allow in this state."""


class Snow:
    def __init__(self, genesis_id: bytes, book):
        self.parent: Dict[bytes, Optional[bytes]] = {genesis_id: None}
        self.height: Dict[bytes, int] = {genesis_id: 0}
        self.state: Dict[bytes, str] = {genesis_id: ACCEPTED}
        self.book = {genesis_id: book}
        self.last_accepted = genesis_id

    # ------------------------------------------------------------ reading
    def status(self, block_id: bytes) -> str:
        return self.state.get(block_id, UNKNOWN)

    def statuses(self) -> Dict[bytes, str]:
        return dict(self.state)

    def accepted_book(self):
        return self.book[self.last_accepted]

    def viable(self, block_id: bytes) -> bool:
        """Consensus can still accept this block (or has): walking up
        from it, every block is processing until the last accepted one
        is reached."""
        while block_id != self.last_accepted:
            if self.status(block_id) != PROCESSING:
                return False
            block_id = self.parent[block_id]
        return True

    def depth(self, block_id: bytes) -> int:
        """Blocks between this one and the last accepted one, itself
        included (0 for the last accepted block)."""
        return self.height[block_id] - self.height[self.last_accepted]

    def legal(self) -> List[Tuple[str, bytes]]:
        """Every call but a new block's ``verify`` that the contract
        allows now: ("accept", id), ("reject", id), and ("verify", id)
        of a block that is known already."""
        calls = []
        for b, s in self.state.items():
            if s == PROCESSING:
                calls.append(("reject", b))
                if self.parent[b] == self.last_accepted:
                    calls.append(("accept", b))
            if self.parent[b] is not None:
                calls.append(("verify", b))
        return calls

    # -------------------------------------------------------- the contract
    def verify(self, block_id: bytes, parent_id: bytes, height: int,
               apply: Optional[Callable] = None) -> None:
        if self.status(block_id) != UNKNOWN:
            return  # known: processing stays so, decided stays decided
        if not self.viable(parent_id):
            raise ContractError("verify on a parent consensus cannot "
                                "accept any more")
        if height != self.height[parent_id] + 1:
            raise ContractError("height is not the parent's plus one")
        book = copy.deepcopy(self.book[parent_id])
        if apply is not None:
            apply(book)
        self.parent[block_id] = parent_id
        self.height[block_id] = height
        self.state[block_id] = PROCESSING
        self.book[block_id] = book

    def accept(self, block_id: bytes) -> None:
        if self.status(block_id) != PROCESSING:
            raise ContractError("accept of a block that is not processing")
        if self.parent[block_id] != self.last_accepted:
            raise ContractError("accept of a block whose parent is not "
                                "the last accepted block")
        self.state[block_id] = ACCEPTED
        self.last_accepted = block_id

    def reject(self, block_id: bytes) -> None:
        if self.status(block_id) != PROCESSING:
            raise ContractError("reject of a block that is not processing")
        self.state[block_id] = REJECTED
        del self.book[block_id]
