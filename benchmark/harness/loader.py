"""Seeded data, loaded as a client loads it: /vol/grow, then
/dir/assign + POST per needle. Copied from chip_smoke.py's `Loader`.

Every needle's bytes come from (seed, volume index, needle index), so a
seed fixes what is stored; the six POST threads fix neither the order
of needles in the `.dat` nor their file ids, which is why the manifest
is kept: the reference reads the sealed `.dat` itself, and holds every
needle in it to the manifest's digest (`needle_digests`).
"""

from __future__ import annotations

import hashlib
import http.client
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.node import Node, http_get, http_json, require


def digest(data) -> bytes:
    return hashlib.blake2b(data, digest_size=16).digest()


def blob(seed: int, vol: int, i: int, size: int) -> bytes:
    return np.random.default_rng((seed, vol, i)).bytes(size)


class Loader:
    def __init__(self, node: Node, seed: int, sizes: list[int]):
        self.node, self.seed, self.sizes = node, seed, sizes
        # vid -> [(fid, size, digest)], in plan order
        self.manifest: dict[int, list[tuple[str, int, bytes]]] = {}

    def fill(self, collection: str, target_bytes: int) -> int:
        """Grow exactly one volume for `collection` and fill it to
        ≈target_bytes. Returns its volume id."""
        grown = http_json(
            f"http://{self.node.master}/vol/grow?collection={collection}&count=1"
        )
        require(grown.get("count") == 1, f"/vol/grow said {grown}")
        vol_idx = len(self.manifest)  # every volume its own seeded stream
        plan, total, i = [], 0, 0
        while total < target_bytes:
            # a few hundred bytes off each class, so that needles land at
            # odd offsets against 1 MiB blocks and 256 KiB decode tiles
            size = self.sizes[i % len(self.sizes)] + (i * 131) % 509
            plan.append((i, size))
            total += size
            i += 1

        def put(item):
            i, size = item
            data = blob(self.seed, vol_idx, i, size)
            a = http_json(
                f"http://{self.node.master}/dir/assign?collection={collection}"
            )
            conn = http.client.HTTPConnection(a["url"], timeout=120)
            try:
                conn.request(
                    "POST", "/" + a["fid"], body=data,
                    headers={"Content-Type": "application/octet-stream"},
                )
                resp = conn.getresponse()
                body = resp.read()
            finally:
                conn.close()
            require(resp.status in (200, 201),
                    f"POST {a['fid']} -> {resp.status} {body[:200]!r}")
            return a["fid"], size, digest(data)

        with ThreadPoolExecutor(max_workers=6) as pool:
            records = list(pool.map(put, plan))
        vids = {int(fid.split(",")[0]) for fid, _, _ in records}
        require(len(vids) == 1, f"{collection} spread over volumes {vids}")
        vid = vids.pop()
        self.manifest[vid] = records
        return vid

    def needle_digests(self, vid: int) -> dict[int, bytes]:
        """Needle id -> digest of what the seed stored under it. A file
        id is `<vid>,<needle id in hex><cookie, 8 hex digits>`."""
        return {int(fid.split(",")[1][:-8], 16): want for fid, _, want in self.manifest[vid]}

    def sample(self, vid: int, n: int) -> list[tuple[str, int, bytes]]:
        """n needles of a volume, every size class represented."""
        records = self.manifest[vid]
        step = max(1, len(records) // n)
        # consecutive records cycle through the size classes; a stride
        # coprime with the cycle length keeps every class in the sample
        while step % len(self.sizes) == 0:
            step += 1
        return records[::step][:n]

    def bodies_differ(self, vid: int, n: int) -> int:
        """GET a sample of the volume's needles; how many bodies differ
        from what was written (a failed GET differs)."""
        bad = 0
        for fid, size, want in self.sample(vid, n):
            try:
                body = http_get(f"http://{self.node.volume}/{fid}")
            except OSError:
                bad += 1
                continue
            if len(body) != size or digest(body) != want:
                bad += 1
        return bad
