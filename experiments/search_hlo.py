"""Compile the store's two search programs for a described TPU v5e, without
a chip, and print one digest of each compiled HLO with its ``metadata={...}``
stripped: the one-chip search over a hamming_prefix layout
(``KNNEngine.search``, as ``chipbench/systems/store.py`` jits it) and the
``v5e:2x2`` hist_merge search (``engine.search_sharded`` over four chips).

    PYTHONPATH=src JAX_PLATFORMS=cpu python experiments/search_hlo.py \\
        [--rows 1048576] [--out DIR]

Two checkouts whose digests agree compile the same programs up to op
metadata (named scopes live there). ``--out`` also writes each stripped HLO
text, for a diff. The repository is the one on ``PYTHONPATH``, so the same
script compares any two checkouts.
"""
from __future__ import annotations

import argparse
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

D, K, Q = 256, 16, 128
_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def strip_metadata(hlo: str) -> str:
    return _METADATA.sub("", hlo)


def programs(rows: int) -> dict:
    """{name: compiled HLO text} of the two cells' search programs, with
    ``jax.default_backend()`` answering "tpu" (``main`` sees to it)."""
    from jax.experimental import topologies

    from repro.core import engine, layout as layout_mod

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    w = D // 32

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt, sh=one: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=sh)
    n_buckets = 1 << layout_mod.default_bits(rows)
    lay = layout_mod.BucketLayout(
        codes=sds((rows, w), jnp.uint32), perm=sds((rows,), jnp.int32),
        inv=sds((rows,), jnp.int32), starts=sds((n_buckets + 1,), jnp.int32))
    one_chip = jax.jit(lambda cc, lo, q: engine.KNNEngine(
        codes=cc, d=D, layout=lo).search(q, K))
    out = {"tagspace-d256": one_chip.lower(
        sds((rows, w), jnp.uint32), lay,
        sds((Q, w), jnp.uint32)).compile().as_text()}

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    four = jax.jit(lambda cc, q: engine.search_sharded(
        cc, q, K, D, mesh, ("data",)))
    out["tagspace-d256-x4"] = four.lower(
        sds((4 * rows, w), jnp.uint32, NamedSharding(mesh, P("data", None))),
        sds((Q, w), jnp.uint32, NamedSharding(mesh, P()))).compile().as_text()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20,
                    help="rows per chip (default 2^20)")
    ap.add_argument("--out", default="",
                    help="directory for the stripped HLO texts")
    args = ap.parse_args(argv)
    # the program asks jax.default_backend() for its kernel geometry and
    # whether to interpret the kernels: answer for the described chip
    jax.default_backend = lambda: "tpu"
    # source locations are debug info too, and the kernels' serialized
    # Mosaic bodies carry theirs outside ``metadata={...}``
    jax.config.update("jax_traceback_in_locations_limit", 0)
    for name, hlo in programs(args.rows).items():
        text = strip_metadata(hlo)
        print(name, hashlib.sha256(text.encode()).hexdigest(), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, name + ".hlo.txt"), "w") as f:
                f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
