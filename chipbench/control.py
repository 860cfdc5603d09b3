"""The control of ``correct``: the run with one stated guarantee broken.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 --seconds 3 [--fault dropped_window]

The configurations state no numeric precision, so the control breaks a
guarantee instead. Each fault is planted in the program's own timed path (on
the engine instance, after it is built) and the rest of the run, the check
included, is ``chipbench.run`` unchanged. Every fault has to come out as
``correct: false``; ``chipbench/tests/test_chipbench.py`` holds that at a
small size and this command shows it on the chip at the cell's own size.

- ``dropped_window``: a window is acknowledged but the table state it
  produced is thrown away (a step that returns its state unchanged); breaks
  "every acknowledged op is applied".
- ``half_batch``: the ops of the upper half of the shards are left out of
  every window the device applies; breaks the same guarantee for half of
  each batch.
- ``answer_altered``: one reply of a sampled block has its version raised
  by one where the future is settled; breaks "replies and versions equal
  the reference's".
- ``lagging_replica``: the last replica's store misses the newest write of
  one key at the final sync; breaks "all R replica stores equal".

No fault leaves out an exchange between chips: on four chips
(``kv-r5-s16384``, since PR 30) the chips split the shard axis, each holds
its shards with all their replicas, and no op's outcome depends on another
chip's. ``half_batch`` there blanks the shards of two of the four chips.
"""

from __future__ import annotations

import argparse
import json
import sys

FAULTS = ("dropped_window", "half_batch", "answer_altered", "lagging_replica")


def _dropped_window(eng, run=None, every: int = 5) -> None:
    """Every ``every``-th write dispatch hands back the state it was given."""
    dev = eng._dev
    calls = {"n": 0}

    def wrap(fn):
        def dispatch(*args, **kw):
            out = fn(*args, **kw)
            calls["n"] += 1
            if calls["n"] % every:
                return out
            state = kw.get("state")
            return (dev.state if state is None else state,) + tuple(out[1:])

        return dispatch

    dev.decide_apply = wrap(dev.decide_apply)
    dev.mixed_apply = wrap(dev.mixed_apply)


def _half_batch(eng, run=None) -> None:
    """Key length 0 means "no op here": blank it for the upper shards."""
    dev = eng._dev
    half = dev.n_shards // 2
    place = dev._place_ops

    def place_half(ops):
        klen = ops.klen.copy()
        klen[:, half:] = 0
        return place(ops._replace(klen=klen))

    dev._place_ops = place_half


class _Altered:
    """A block's replies with shard 0's version raised by one."""

    def __init__(self, results) -> None:
        self.results = results

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        for j in range(len(self.results)):
            yield self[j]

    def __getitem__(self, j):
        frames = [bytes(f) for f in self.results[j]]
        if j == 0:
            f = bytearray(frames[0])
            f[1] = (f[1] + 1) & 0xFF
            frames[0] = bytes(f)
        return frames


def _answer_altered(eng, run) -> None:
    """The first sampled block submitted in the window has shard 0's reply
    altered as soon as the engine has settled its future, before the client
    looks at it."""
    submit, cycle = eng.submit_block, eng.run_cycle
    target = []

    def submit_block(block):
        fut = submit(block)
        if not target and run.measuring and run.picked(len(run.stream) - 1):
            target.append(fut)
        return fut

    def run_cycle():
        out = cycle()
        if len(target) == 1 and target[0].done():
            target[0]._results = _Altered(target[0]._results)
            target.append(None)
        return out

    eng.submit_block, eng.run_cycle = submit_block, run_cycle


def _lagging_replica(eng, run=None) -> None:
    dev = eng._dev
    sync_into = dev.sync_into
    last = eng.sms[-1]

    def lagging(sm, dump=None):
        d = dump if dump is not None else dev.dump()
        if sm is last and d["rows"]:
            s, key, val, ver = d["rows"][0]
            d = dict(d, rows=[(s, key, val, ver - 1)] + list(d["rows"][1:]))
        return sync_into(sm, dump=d)

    dev.sync_into = lagging


_PLANT = {
    "dropped_window": _dropped_window,
    "half_batch": _half_batch,
    "answer_altered": _answer_altered,
    "lagging_replica": _lagging_replica,
}


def plant(fault: str):
    """``engine_hook(eng, runner)`` for ``run.run_cell`` that plants ``fault``."""
    return _PLANT[fault]


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", choices=FAULTS + ("none",), default="dropped_window")
    args = ap.parse_args(argv)
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        hook = None if args.fault == "none" else plant(args.fault)
        result = run.run_cell(
            args.workload, seed, args.seconds, False, engine_hook=hook
        )
        line = {
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "correct": result["correct"],
            "checks": {k: v["value"] for k, v in result["checks"].items()},
            "window": result["window"],
        }
        print(json.dumps(line), flush=True)
        if result["correct"] != (args.fault == "none"):
            rc = 1  # a fault that passes, or a sound run that fails
    return rc


if __name__ == "__main__":
    sys.exit(main())
