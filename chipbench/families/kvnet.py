"""The ``kvnet`` family: the ``kv`` family's deployment under upstream's lossy
network (``rabia-testing``'s ``lossy_network``), where every replica
proposes and weak-MVC decides over views of n - f senders, the common coin
and null slots included.

The traffic, the wire, the engine's parts and the replies are ``kv``'s
(``families/kv.py``): the ops and their answers do not depend on the
network, since weak-MVC never reorders a shard's ops. What the network
changes is which slot each op takes, so the replica comparison adds the
decision check: for ``DECISION_SHARDS`` shards drawn from the seed, every
slot the engine's decision log holds (``decisions_for``) must equal the
slot ``reference/kvnet_plain.py`` decides, and the shard's next slot must
be the one after the reference's slot for its last op (its ops plus the
reference's null slots among them). Each slot that differs, and each
shard's count, is a mismatch. The network model states every replica up,
so a traffic file with a fault schedule is refused. Nothing here imports
the program at import time.
"""

from __future__ import annotations

import numpy as np

from chipbench import gen
from chipbench.families import kv
from chipbench.reference import load_reference
from chipbench.spec import SpecError

DECISION_SHARDS = 64  # shards whose every slot the check decides again

engine_parts = kv.engine_parts
replies = kv.replies


class Generator(gen.Generator):
    """``kv``'s generator, which keeps its seed for the decision check's
    sample of shards."""

    def __init__(self, seed: int, config: dict, traffic: dict) -> None:
        super().__init__(seed, config, traffic)
        self.seed = int(seed)


def validate(traffic: dict) -> None:
    kv.validate(traffic)
    if traffic.get("faults"):
        raise SpecError(
            f"{traffic.get('name', '?')}: the kvnet family's network model "
            "states every replica up; it takes no fault schedule"
        )


def reference(config: dict, generator):
    net = config["engine"]
    return load_reference(config)(
        generator.S, generator.n_keys, generator.VW,
        n_replicas=int(config["n_replicas"]), seed=int(net.get("seed", 0)),
        loss=float(net["net_loss"]),
    )


def sampled_shards(generator) -> np.ndarray:
    rng = np.random.default_rng([generator.seed & (2**63 - 1), 0x6B766E6574])
    n = min(DECISION_SHARDS, generator.S)
    return np.sort(rng.choice(generator.S, n, replace=False))


def decision_mismatches(eng, ref, generator) -> tuple:
    """(slots compared, slots and counts that differ, the first
    difference) over the sampled shards."""
    shards = sampled_shards(generator)
    want = ref.net.slots_for(shards, ref.waves)
    compared = wrong = 0
    first = ""
    for s, slots in zip(shards.tolist(), want):
        log = eng.decisions_for(s)
        bad = int(int(eng.next_slot[s]) != len(slots))
        if bad:
            first = first or (f"shard {s}: next slot {int(eng.next_slot[s])}, "
                              f"reference {len(slots)}")
        for k, (v, _batch) in log.items():
            compared += 1
            if k >= len(slots) or int(v) != int(slots[k]):
                bad += 1
                first = first or (
                    f"shard {s} slot {k}: decided {int(v)}, reference "
                    f"{int(slots[k]) if k < len(slots) else 'no such slot'}"
                )
        wrong += bad
    return compared, wrong, first


def replica_mismatches(eng, ref, generator) -> tuple:
    replicas, wrong, first = kv.replica_mismatches(eng, ref, generator)
    compared, bad, why = decision_mismatches(eng, ref, generator)
    if not compared:
        bad += 1
        why = why or "the engine's decision log holds no slot of a sampled shard"
    return replicas, wrong + bad, first or why
