"""Cache of the encoded head, inside the checkout.

Encoding the head on the host is most of a run's set-up (about 6.5 us per
nonzero).  The head is made from the configuration's own ``head.seed``,
the same for every run, so a checkout encodes it once, in its first run,
which also compiles; every later run loads it and does the same set-up
work, whatever its ``--seed``.  The key is a hash of every file under
``src/`` and of the configuration (sizes, compression settings and the
head's seed), so a change to the program never reads a stale artifact.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

from bench.spec import BENCH, ROOT

CACHE = BENCH / ".cache" / "heads"


def source_hash() -> str:
    h = hashlib.sha256()
    src = ROOT / "src"
    for path in sorted(p for p in src.rglob("*") if p.is_file()
                       and "__pycache__" not in p.parts):
        h.update(str(path.relative_to(src)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def key(cfg: dict) -> str:
    blob = json.dumps({"src": source_hash(), "config": cfg}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def load_or_build(cfg: dict, build) -> tuple[object, dict]:
    """The encoded head of this configuration and what it cost:
    ``{"cached": bool, "encode_s": seconds spent encoding or 0}``."""
    path = CACHE / f"{key(cfg)}.pkl"
    if path.is_file():
        with open(path, "rb") as f:       # written by this benchmark only
            return pickle.load(f), {"cached": True, "encode_s": 0.0}
    t0 = time.perf_counter()
    head = build()
    encode_s = time.perf_counter() - t0
    CACHE.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".tmp{os.getpid()}")
    with open(tmp, "wb") as f:
        pickle.dump(head, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return head, {"cached": False, "encode_s": encode_s}
