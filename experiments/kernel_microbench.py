"""Per-face pack/unpack kernel microbench on the real chip: XLA slice/DUS vs
per-row window-DMA Pallas kernel vs batched-row prefetching kernel
(ops/halo_pallas.py).

Measurement method — two pitfalls probed on the round-5 backend:

* ``block_until_ready`` returns before device execution completed on that
  backend (the library benchmarker already knows this,
  bench/benchmarker.py:20-25), so every timing is fenced by a device->host
  fetch of one element of the result.
* a single kernel dispatch cost a ~6-12 ms round trip there, far above the
  0.1-5 ms kernels being compared, so each measurement runs a K-length
  ``fori_loop`` chain of data-dependent applications inside ONE program and
  reports the (K_hi - K_lo) wall-time slope — fixed dispatch+fetch overhead
  cancels.

Findings at the flagship geometry (written to KERNEL_MICROBENCH.json): the
unpack kernel family is face-direction-dependent by >20x — XLA's aliased
narrow DUS wins z-faces (no lane-tile window amplification), the Pallas
window kernels win y-faces by ~4x, i.e. exactly the storage-order
kernel-family question the menu exposes to the search.

Run on the TPU: python experiments/kernel_microbench.py   (TZ_FACES=xyz)
"""

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

K_LO, K_HI = 4, 44
REPS = 9


def main():
    import jax

    from tenzing_tpu.bench.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import jax.lax as lax

    from tenzing_tpu.models.halo import HaloArgs, _face_slices, dir_name
    from tenzing_tpu.models.halo_pipeline import _padded_shape
    from tenzing_tpu.ops.halo_pallas import (
        _face_bx,
        pack_face_pallas,
        pack_face_pallas_batched,
        unpack_face_pallas,
        unpack_face_pallas_batched,
    )

    n = 512
    args = HaloArgs(nq=3, lx=n, ly=n, lz=n, radius=3)
    rng = np.random.default_rng(0)
    pad = _padded_shape(args.local_shape())
    U0 = jnp.asarray(rng.random(pad, dtype=np.float32))

    def slope(mk_chain):
        """(wall(K_HI) - wall(K_LO)) / (K_HI - K_LO), median over REPS,
        each wall fetch-fenced."""
        walls = {}
        for K in (K_LO, K_HI):
            cj = jax.jit(mk_chain(K))
            float(cj(U0, jnp.float32(0.0)))  # warm / compile
            ts = []
            for i in range(REPS):
                t0 = time.perf_counter()
                float(cj(U0, jnp.float32(i + 1.0)))
                ts.append(time.perf_counter() - t0)
            walls[K] = float(np.median(ts))
        return (walls[K_HI] - walls[K_LO]) / (K_HI - K_LO)

    out = {"config": {"nq": 3, "n": n, "radius": 3, "padded": list(pad)},
           "method": f"fetch-fenced fori_loop chain slope K={K_LO}->{K_HI}, "
                     f"median of {REPS}",
           "faces": {}}
    axes = {"x": (1, 0, 0), "y": (0, 1, 0), "z": (0, 0, 1)}
    # one face per axis sign-class is enough (±d are geometrically congruent)
    for a in os.environ.get("TZ_FACES", "xyz"):
        d = axes[a]
        ps, sz = _face_slices(args, d, "pack")
        us, _ = _face_slices(args, d, "unpack")
        ps, sz, us = tuple(ps), tuple(sz), tuple(us)
        face0 = jnp.asarray(rng.random(sz, dtype=np.float32))

        # numerics first (device-side compare: np round-trips 2 GB to
        # the host)
        want_p = lax.dynamic_slice(U0, ps, sz)
        for fn, nm in [(pack_face_pallas, "row"),
                       (pack_face_pallas_batched, "batched")]:
            assert bool(jnp.allclose(fn(U0, ps, sz), want_p)), f"pack {nm} {d}"
        want_u = lax.dynamic_update_slice(U0, face0, us)
        for fn, nm in [(unpack_face_pallas, "row"),
                       (unpack_face_pallas_batched, "batched")]:
            assert bool(jnp.allclose(fn(U0, face0, us), want_u)), \
                f"unpack {nm} {d}"
        del want_p, want_u

        unpacks = {
            "xla": lambda U, f: lax.dynamic_update_slice(U, f, us),
            "row": lambda U, f: unpack_face_pallas(U, f, us),
            "batched": lambda U, f: unpack_face_pallas_batched(U, f, us),
        }
        packs = {
            "xla": lambda U: lax.dynamic_slice(U, ps, sz),
            "row": lambda U: pack_face_pallas(U, ps, sz),
            "batched": lambda U: pack_face_pallas_batched(U, ps, sz),
        }
        r = {"bx": _face_bx(args, d),
             "face_mb": round(float(np.prod(sz)) * 4 / 1e6, 2)}
        for nm, kern in unpacks.items():
            def mk_chain(K, kern=kern):
                def chain(U, s):
                    def body(t, Uc):
                        return kern(Uc, face0 + s + jnp.float32(t))
                    Uo = lax.fori_loop(0, K, body, U)
                    return Uo[0, us[1], us[2], us[3]]
                return chain
            r[f"unpack_{nm}_ms"] = round(slope(mk_chain) * 1e3, 4)
        # pack alone can't be chained (static starts -> a pack-only loop body
        # is loop-invariant and XLA hoists it); chain the pack∘unpack round
        # trip each schedule actually uses (pack reads the interior edge,
        # unpack writes the disjoint ghost shell, so the composition neither
        # converges nor self-feeds) and derive pack = roundtrip - unpack
        for nm in unpacks:
            pk, up = packs[nm], unpacks[nm]

            def mk_chain(K, pk=pk, up=up):
                def chain(U, s):
                    def body(t, Uc):
                        return up(Uc, pk(Uc) + s + jnp.float32(t))
                    Uo = lax.fori_loop(0, K, body, U)
                    return Uo[0, us[1], us[2], us[3]]
                return chain
            rt = slope(mk_chain) * 1e3
            r[f"roundtrip_{nm}_ms"] = round(rt, 4)
            r[f"pack_{nm}_ms_derived"] = round(rt - r[f"unpack_{nm}_ms"], 4)
        out["faces"][dir_name(d)] = r
        print(dir_name(d), json.dumps(r), flush=True)

    path = Path(__file__).parent / "KERNEL_MICROBENCH.json"
    if path.exists():
        prev = json.loads(path.read_text())
        if (prev.get("method"), prev.get("config")) == (out["method"],
                                                        out["config"]):
            prev["faces"].update(out["faces"])
            out = prev
    path.write_text(json.dumps(out, indent=1))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
