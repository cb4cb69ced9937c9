#!/usr/bin/env python3
"""The forms an eviction's two searches can take, timed at the drained cell's
own shape on one chip (PERF.md §6, PR 45): not the program, stand-ins of the
same shapes on made-up data (128 scenarios; 337,431 places of the placement
buffer and 22 x 8,192 of the record, 517,655 together; 64 leaving nodes of
10,000; about 1,500 victims a scenario).

* which binds stand on a leaving node: ``compare`` (every place against the
  leaving nodes, the program's form) against ``gather`` (a [N] mask read by
  the place's node: a gather a scenario);
* bringing the victims to the front: ``sort_payload`` (one sort of key and
  source over all places, the program's form), ``sort_keys`` (the key
  alone), ``nonzero`` (``jnp.nonzero(size=E)``: a cumulative sum and a
  scatter), ``ranks`` (two levels: blocks of 128 places counted, an output
  slot finds its block by comparing with the blocks' offsets, reads that
  block's row and takes the lane of its rank; no sort, no scatter).

One JSON line: ms a call, median of five, after a warm-up. On the chip:

    python3 benchmark/tests/chip_forms_drain.py
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

S, V, REC, N, L, E = 128, 337_431, 22 * 8_192, 10_000, 64, 4_096
M = -(-(V + REC) // 4096) * 4096  # the places, padded to whole blocks


def data(seed=0):
    rng = np.random.default_rng(seed)
    place = rng.integers(-1, N, size=(S, M), dtype=np.int32)
    leave = np.stack([rng.choice(N, size=L, replace=False) for _ in range(S)])
    return jnp.asarray(place), jnp.asarray(leave.astype(np.int32))


def by_compare(place, leave):
    return ((place[..., None] == leave[:, None, :]).any(-1)) & (place >= 0)


def by_gather(place, leave):
    mask = jnp.zeros((S, N), bool).at[jnp.arange(S)[:, None], leave].set(True)
    return jnp.take_along_axis(mask, jnp.clip(place, 0), axis=1) & (place >= 0)


def keys_of(hit):
    at = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), hit.shape)
    return jnp.where(hit, at, jnp.iinfo(jnp.int32).max), at


def sort_payload(hit):
    k, at = keys_of(hit)
    k, at = jax.lax.sort((k, at), dimension=1, num_keys=1, is_stable=False)
    return jnp.where(k < jnp.iinfo(jnp.int32).max, at, M)[:, :E]


def sort_keys(hit):
    k = jax.lax.sort(keys_of(hit)[0], dimension=1)[:, :E]
    return jnp.where(k < jnp.iinfo(jnp.int32).max, k, M)


def nonzero(hit):
    return jax.vmap(lambda h: jnp.nonzero(h, size=E, fill_value=M)[0])(hit)


def ranks(hit):
    def one(h):
        rows = h.reshape(M // 128, 128)
        count = rows.sum(1, dtype=jnp.int32)
        start = jnp.cumsum(count) - count
        slot = jnp.arange(E, dtype=jnp.int32)
        block = (start[None, :] <= slot[:, None]).sum(1, dtype=jnp.int32) - 1
        rank = slot - start[block]
        row = rows[block]
        upto = jnp.cumsum(row.astype(jnp.int32), axis=1)
        lane = jnp.argmax((upto == rank[:, None] + 1) & row, axis=1)
        return jnp.where(slot < count.sum(), block * 128 + lane, M)

    return jax.vmap(one)(hit)


def ms(fn, *args):
    f = jax.jit(fn)
    out = jax.block_until_ready(f(*args))
    took = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        took.append(1e3 * (time.perf_counter() - t))
    return statistics.median(took), out


def main() -> int:
    if jax.devices()[0].platform != "tpu" and "--rehearse" not in sys.argv:
        print("no TPU: forms off the chip need --rehearse", file=sys.stderr)
        return 1
    place, leave = data()
    line = {"platform": jax.devices()[0].platform, "shape": [S, M, L, E]}
    line["mask_compare_ms"], hit = ms(by_compare, place, leave)
    line["mask_gather_ms"], hit2 = ms(by_gather, place, leave)
    assert bool((hit == hit2).all())
    line["victims_mean"] = float(hit.sum(1).mean())
    want = None
    for name, fn in (("sort_payload", sort_payload), ("sort_keys", sort_keys),
                     ("nonzero", nonzero), ("ranks", ranks)):
        line[name + "_ms"], got = ms(fn, hit)
        want = got if want is None else want
        assert bool((got == want).all()), name
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
