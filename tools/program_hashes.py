#!/usr/bin/env python3
"""Are two trees' programs the same programs?

    JAX_DUMP_IR_TO=<dir> JAX_INCLUDE_DEBUG_INFO_IN_DUMPS=false <any run>
    python tools/program_hashes.py <dir> [<other dir>]

With those two variables jax writes every module it hands to the compiler
(persistent-cache hit or not) into ``<dir>`` as StableHLO text without
source locations, one file a module, named by a per-process counter and the
module's name. This hashes each file and prints, per module name, the
hashes in the order they were lowered; given two directories it prints what
differs and exits 1 if anything does. A refactor that moves code and keeps
every program (PR 29's KV-cache move) shows an empty difference on the CPU
at a tiny size and on the chip at a cell's size.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys
from collections import Counter
from typing import Dict, List

NAME = re.compile(r"^jax_ir\d+_(.+)_compile\.mlir$")


def hashes(directory: str) -> Dict[str, List[str]]:
    """Module name -> hashes of its lowerings, in lowering order."""
    out: Dict[str, List[str]] = {}
    for fname in sorted(os.listdir(directory)):
        m = NAME.match(fname)
        if m:
            with open(os.path.join(directory, fname), "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            out.setdefault(m.group(1), []).append(digest)
    return out


def main(argv: List[str]) -> int:
    if len(argv) not in (1, 2):
        print(__doc__, file=sys.stderr)
        return 2
    a = hashes(argv[0])
    if len(argv) == 1:
        json.dump(a, sys.stdout, indent=1, sort_keys=True)
        print()
        return 0
    b = hashes(argv[1])
    # as multisets: two runs may lower the same modules in another order
    differ = {}
    for name in sorted(set(a) | set(b)):
        first, second = Counter(a.get(name, [])), Counter(b.get(name, []))
        if first != second:
            differ[name] = {
                "only_in_first": sorted((first - second).elements()),
                "only_in_second": sorted((second - first).elements()),
            }
    print(json.dumps({
        "modules_first": sum(map(len, a.values())),
        "modules_second": sum(map(len, b.values())),
        "names": len(set(a) | set(b)),
        "differ": differ,
    }, indent=1, sort_keys=True))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
