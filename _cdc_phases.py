"""CDC e2e phase attribution at the bench shape (1 GiB slab).

Times each stage of the fast path separately, every device stage fenced
by fetching a scalar reduction of its output:

  A. gear kernel, native layout (no transposes)
  B. gear kernel via gear_candidates_pallas (input+output transposes)
  C. full _extract_first_occ (kernel + window reduce + occ/offs pack)
  D. full candidates_begin().collect() (adds D2H + host unpack/nonzero)
  E. D + native greedy select (the whole e2e leg)
"""
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from dat_replication_protocol_tpu.ops import rabin
from dat_replication_protocol_tpu.ops.rabin_pallas import (
    gear_candidates_native,
    gear_candidates_pallas,
)
from dat_replication_protocol_tpu.utils.cache import enable_compile_cache
from dat_replication_protocol_tpu.utils.chiplock import chip_lock

enable_compile_cache()

# diagnostics must never share the chip with a bench capture (round-4
# lesson); held for the process lifetime, released by the kernel on exit
_lock_cm = chip_lock()  # keep the CM alive: a bare __enter__() on a
# temporary would be GC'd, running the generator's finally and RELEASING
# the flock immediately (caught in round-5 review)
_lease = _lock_cm.__enter__()
print(f"chip lock: uncontended={_lease.uncontended}", flush=True)

slab_b = 1 << 30
stride = 1 << 17
T = slab_b // stride
avg_bits = 13
thin_bits = avg_bits - 2

words = jax.random.bits(jax.random.PRNGKey(5), (slab_b // 4,), dtype=jnp.uint32)
jax.block_until_ready(words)

# pre-transposed native-layout input (with the prefix rows the real path
# builds): rows (T, _PREFIX_WORDS + stride/4)
rows_flat = rabin._build_rows(
    words.reshape(T, stride // 4).reshape(-1),
    jnp.zeros((rabin._PREFIX_WORDS,), jnp.uint32), T, stride,
)
S = rows_flat.shape[1] * 4
ng = S // rabin.GROUP
native = jnp.transpose(
    rows_flat.reshape(T, ng, rabin.GROUP // 4), (1, 2, 0)
).reshape(ng, rabin.GROUP // 4, 8, T // 8)
native = jax.device_put(native)
jax.block_until_ready(native)


def timed(tag, fn, reps=3):
    fn()
    dts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dts.append(time.perf_counter() - t0)
    med = statistics.median(dts)
    print(f"{tag}: {med*1e3:.1f} ms ({slab_b / med / (1<<30):.2f} GiB/s)",
          flush=True)
    return med


kern_n = jax.jit(lambda w: jnp.sum(gear_candidates_native(w, avg_bits)))
timed("A kernel native-layout", lambda: np.asarray(kern_n(native)))

kern_p = jax.jit(lambda r: jnp.sum(gear_candidates_pallas(r, avg_bits)))
timed("B kernel via pallas wrapper (transposes)",
      lambda: np.asarray(kern_p(rows_flat)))

pre = jnp.zeros((rabin._PREFIX_WORDS,), jnp.uint32)
cap0 = min(max(256, slab_b >> max(avg_bits - 2, 0)), slab_b >> thin_bits)


def extract_fenced():
    occ, offs = rabin._extract_first_occ(
        words, pre, T, stride, avg_bits, cap0, True, thin_bits,
        route="bitmask",
    )
    np.asarray(jnp.sum(occ) + jnp.sum(offs.astype(jnp.uint32)))


timed("C extract_first_occ fenced on device", extract_fenced)

timed("D candidates collect (D2H + host)",
      lambda: rabin.candidates_begin(words, slab_b, avg_bits,
                                     thin_bits=thin_bits)())


def e2e():
    c = rabin.candidates_begin(words, slab_b, avg_bits, thin_bits=thin_bits)
    rabin._greedy_select(c(), slab_b, 1 << (avg_bits - 2),
                         1 << (avg_bits + 2))


timed("E full e2e (collect + greedy)", e2e)

# sub-attribution of the extraction tail: window-reduce alone, in both
# layouts (the transposed (T,S/PACK) one the code uses today vs a
# native-layout leading-axis reduce)
bits_n = gear_candidates_native(native, avg_bits)
jax.block_until_ready(bits_n)
gpw = (1 << thin_bits) // rabin.GROUP  # groups per window


@jax.jit
def reduce_native(bits):
    # (ng, 8, 8, T/8): drop warm-up group 0, then windows of gpw groups
    v = bits[1:]
    nwpt = (ng - 1) // gpw
    v = v.reshape(nwpt, gpw * (rabin.GROUP // rabin.PACK), 8, T // 8)
    # first-set-bit across axis 1 in stream word order, elementwise lanes
    wnz = v != jnp.uint32(0)
    first_w = jnp.argmax(wnz, axis=1).astype(jnp.int32)
    wval = jnp.take_along_axis(v, first_w[:, None], axis=1)[:, 0]
    lsb = wval & (jnp.uint32(0) - wval)
    bitpos = rabin._popcount32(lsb - jnp.uint32(1)).astype(jnp.int32)
    inwin = jnp.where(
        jnp.any(wnz, axis=1),
        first_w * rabin.PACK + bitpos, 1 << 30,
    )
    return jnp.sum(jnp.where(inwin < (1 << 30), inwin, 0))


timed("F window-reduce native-layout (fenced)",
      lambda: np.asarray(reduce_native(bits_n)))

bits_t = gear_candidates_pallas(rows_flat, avg_bits)
jax.block_until_ready(bits_t)
wpw = (1 << thin_bits) // rabin.PACK


@jax.jit
def reduce_transposed(bits):
    vw = bits[:, rabin._PREFIX // rabin.PACK:
              rabin._PREFIX // rabin.PACK + stride // rabin.PACK]
    first = rabin._first_bit_per_window(vw.reshape(-1, wpw))
    return jnp.sum(jnp.where(first < (1 << 30), first, 0))


timed("G window-reduce transposed-layout (fenced)",
      lambda: np.asarray(reduce_transposed(bits_t)))
