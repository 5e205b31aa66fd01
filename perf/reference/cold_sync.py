"""The plain reference of `relay-restore`: a device restored from its
mnemonic pulls its owner's whole history from the reference relay in one
answer (upstream's `restoreOwner.ts`, then the first round of
`sync.worker.ts` against `apps/server/src/index.ts:121-216`) and merges it
(`receive.ts`, `applyMessages.ts:26-131`).

It is the two plain references composed, named here and not copied:
`perf/reference/relay_sync.py::ReferenceRelay` holds the owner's rows
(upstream's `addMessages`, one message at a time) and answers `sync` for
the empty client tree `"{}"` with every row the device did not write
(`getMessages`), and `perf/reference/client_todo.py::ReferenceClient`
takes that answer in ONE `receive` at the device's pinned `now`. Nothing
here is the program's. The ciphertexts stay opaque: the answer's rows are
read back to their cells through `plain`, the generator's own record of
what it encrypted.
"""

from perf import load_module

_relay = load_module("reference", "relay_sync")
_client = load_module("reference", "client_todo")
ReferenceRelay = _relay.ReferenceRelay
ReferenceClient = _client.ReferenceClient


def restore(owner: str, rows, plain: dict, tables, mnemonic: str, node: str,
            now: int) -> dict:
    """`rows` are the owner's (timestamp, ciphertext) in the order the
    relay received them; `plain` maps a ciphertext to its (table, row,
    column, value). → {"dump": the device's `client_todo.dump`, "answered":
    the rows the one answer carried, "tree": the relay's tree string in
    it, "relay": the relay's `owner_dump` of the owner after the pull}."""
    relay = ReferenceRelay()
    try:
        relay.add_messages(owner, rows, relay.get_merkle_tree(owner))
        answered, tree = relay.sync(owner, node, [], "{}")
        device = ReferenceClient(tables, mnemonic, node)
        try:
            device.receive([(t, *plain[c]) for t, c in answered], now)
            dump = device.dump()
        finally:
            device.close()
        return {"dump": dump, "answered": answered, "tree": tree,
                "relay": relay.owner_dump(owner)}
    finally:
        relay.close()
