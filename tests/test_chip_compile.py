"""Compile the main path's kernels for a described TPU v5e chip — what
the chip's compiler would refuse (an SMEM window over its limit, a
VMEM scratch too large, a tile-misaligned slice) fails here, at no
chip time, instead of silently falling back on the chip.

Nothing runs: the kernels are lowered from shapes and compiled for a
``v5e:2x2`` topology that is described, not attached. The geometries
are the ones the chip smoke's phases dispatch (``chip_smoke.py``):
the cas-100k history (73k returns, W=5, M=32, S=8) on the lane and
chunk-lockstep engines and the word walk, the 4096-key x 100-op
batch on the lockstep and keyed kernels, and the wide-M lockstep
kernel at the mask widths crashed ops give keyed keys (W=11-14). Every geometry is derived
through the production sizing helpers, so a change there is compiled
here.

The topology is described inside a fixture (never at import): only
one process may load libtpu, and xdist workers import every test
file.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from jepsen_tpu.checkers import reach, reach_batch, reach_chunklock, \
    reach_lane, reach_word, transfer  # noqa: E402

# the cas-100k history of the chip smoke: returns, slots, masks,
# states, and transition rows (op alphabet + the sentinel row)
R_100K, W, M, S, O1 = 73_438, 5, 32, 8, 37
# the 4096-key x 100-op batch: lockstep lanes per group, the longest
# key's returns, and the keys' returns in all
KEYS, H, R_KEY, R_KEYS = 4096, 32, 100, 4096 * 75


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return fn.lower(*args).compile()


def _sextet(n: int):
    """The 6-bit packed ops lane of ``n`` values, as the wire has it."""
    return (transfer.sextet_bytes(n),), jnp.uint8


def test_lane_kernel_cas_100k(one_chip):
    """The single-history lane kernel: one pipelined segment of the
    cas-100k walk, packed wire (6-bit ops lane, bit-packed seed)."""
    B = reach_lane._BLOCK
    R_pad = reach._bucket(-(-R_100K // B) * B, B)
    seg, _ = reach_lane._pipe_geom(B, R_pad)
    run = reach_lane._lane_call(B, W, M, S, O1, seg, W, False)
    _compile(run, one_chip, ((seg,), jnp.int8), _sextet(seg * W),
             ((O1, S, S), jnp.float32), ((M * S // 8,), jnp.uint8))


def _chunklock_geometry():
    """Phase A / phase B batch-kernel geometries of the cas-100k
    chunk-lockstep walk, through the engine's own sizing rules."""
    C = reach_chunklock._auto_chunks(S, R_100K)
    e_pad = reach_chunklock._E_PAD
    blk = min(reach_lane._BLOCK, reach_batch._adaptive_block(C, W))
    per = -(-R_100K // C)
    per_pad = -(-per // blk) * blk
    L = min(reach_chunklock._SUFFIX, per)
    b_a = min(blk, L)
    seg_b, _ = reach_lane._pipe_geom(blk, per_pad,
                                     reach_batch._PIPE_NSEG)
    return C, e_pad, blk, b_a, -(-L // b_a) * b_a, seg_b


@pytest.mark.parametrize("phase", ["A", "B"])
def test_batch_kernel_chunklock_cas_100k(one_chip, phase):
    """The lockstep batch kernel at the chunk-lockstep engine's phase
    A (suffix bound pass from the full config set) and phase B (the
    e_pad-seeded transfer pass, one segment) geometries."""
    C, e_pad, blk, b_a, L_pad, seg_b = _chunklock_geometry()
    cdt = reach_batch._COMPUTE_DTYPE
    HS = C * S
    if phase == "A":
        run = reach_batch._batch_call(b_a, W, M, S, C, O1, L_pad, W,
                                      False, cdt)
        _compile(run, one_chip, ((L_pad * C * W,), jnp.int8),
                 ((L_pad, C), jnp.int8), ((O1, S, S), jnp.float32),
                 ((M * HS // 8,), jnp.uint8))
    else:
        Mb = e_pad * M
        run = reach_batch._batch_call(blk, W, Mb, S, C, O1, seg_b, W,
                                      False, cdt)
        _compile(run, one_chip, _sextet(seg_b * C * W),
                 ((seg_b, C), jnp.int8), ((O1, S, S), jnp.bfloat16),
                 ((Mb, HS), jnp.bfloat16))


def test_chunklock_glue_and_fold_cas_100k(one_chip):
    """The XLA glue (phase A sets -> seeds) and fold (images -> the
    one packed verdict array) of the chunk-lockstep walk."""
    C, e_pad, *_ = _chunklock_geometry()
    _compile(reach_chunklock._glue_call(C, M, S, e_pad), one_chip,
             ((M, C * S), jnp.float32))
    _compile(reach_chunklock._fold_call(C, M, S, e_pad), one_chip,
             ((e_pad * M, C * S), jnp.float32),
             ((C, e_pad, M * S), jnp.float32), ((C,), jnp.int32))


def test_batch_kernel_keyed_batch(one_chip):
    """The lockstep batch kernel at the 4096-key batch's group
    geometry: H lanes of one-block keys, f32 below the 128-lane
    tile, bf16 at it."""
    B, R_pad = reach_batch.group_geom(R_KEY, H, W)
    seg, _ = reach_lane._pipe_geom(B, R_pad, reach_batch._PIPE_NSEG)
    cdt = reach_batch._COMPUTE_DTYPE if H * S >= 128 else "float32"
    run = reach_batch._batch_call(B, W, M, S, H, O1, seg, W, False, cdt)
    # P crosses the wire already in the compute dtype
    _compile(run, one_chip, _sextet(seg * H * W), ((seg, H), jnp.int8),
             ((O1, S, S), jnp.dtype(cdt)), ((M * H * S // 8,), jnp.uint8))


@pytest.mark.parametrize("w", [11, 12, 13, 14])
def test_batch_kernel_wide_keys(one_chip, w):
    """The lockstep kernel at the geometry ``lockstep_geometry`` picks
    for keys whose crashed ops hold slots to the end (the partition
    deployment's W 11-14): the wide-M kernel, its config set in VMEM
    under the scoped limit it asks for, one block of 100-op keys."""
    geo = reach_batch.lockstep_geometry(w, S)
    assert geo.wide
    m = 1 << w
    B, R_pad = reach_batch.group_geom(R_KEY, geo.H, w)
    cdt = reach_batch._COMPUTE_DTYPE if geo.H * S >= 128 else "float32"
    run = reach_batch._batch_call(
        B, w, m, S, geo.H, O1, R_pad, min(w, reach_lane._FAST_PASSES),
        False, cdt, tile=reach_batch._wide_tile(w, m, S))
    _compile(run, one_chip, _sextet(R_pad * geo.H * w),
             ((R_pad, geo.H), jnp.int8), ((O1, S, S), jnp.dtype(cdt)),
             ((m * geo.H * S // 8,), jnp.uint8))


def _batch_at_block(sharding, B: int, lanes: int, states: int):
    """Compile the batch kernel at block ``B`` (W=5, two blocks)."""
    R_pad = 2 * B
    run = reach_batch._batch_call(B, W, M, states, lanes, O1, R_pad, W,
                                  False, reach_batch._COMPUTE_DTYPE)
    _compile(run, sharding, ((R_pad * lanes * W,), jnp.int8),
             ((R_pad, lanes), jnp.int8),
             ((O1, states, states), jnp.float32),
             ((M * lanes * states // 8,), jnp.uint8))


@pytest.mark.parametrize("lanes,states", [(32, 8), (64, 4)])
def test_adaptive_block_compiles(one_chip, lanes, states):
    """``_adaptive_block`` is the only guard of the chip's 1 MiB SMEM
    limit on the batch kernel's double-buffered slot_ops window: the
    block it picks compiles at 32 and 64 lanes, and at the max_slots
    cap (W=20, which sizes ``plan_buckets``' floor bucket) its window
    stays inside the budget too. (W=20 itself cannot be compiled: the
    dense kernel needs 2**W config rows, which ``_pallas_fits`` refuses
    long before the SMEM window matters.)"""
    for w in (W, 20):
        B = reach_batch._adaptive_block(lanes, w)
        assert B * lanes * w * 4 * 2 <= reach_batch._SMEM_BUDGET < 1 << 20
    _batch_at_block(one_chip, reach_batch._adaptive_block(lanes, W),
                    lanes, states)


def test_double_block_exceeds_smem(one_chip):
    """The budget is the real limit: twice the chosen block at 32
    lanes (B=1024, a 1.25 MiB window) is refused by the compiler."""
    B = 2 * reach_batch._adaptive_block(32, W)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _batch_at_block(one_chip, B, 32, 8)


def test_keyed_kernel_keyed_batch(one_chip):
    """The keyed flat-stream kernel over the 4096 keys' concatenated
    returns (the single-chip keyed lane)."""
    B = reach_lane._BLOCK
    N_pad = reach._bucket(-(-R_KEYS // B) * B, B)
    K_pad = reach._bucket(KEYS, 8)
    run = reach_lane._keyed_call(B, W, M, S, O1, N_pad, K_pad, W, False)
    _compile(run, one_chip, ((N_pad,), jnp.int8), _sextet(N_pad * W),
             ((N_pad,), jnp.int16), ((O1, S, S), jnp.float32))


def test_word_walk_cas_100k(one_chip):
    """The word-packed post-hoc walk at the cas-100k geometry (blocks
    padded to a power of two, one uint32 word per state)."""
    n_pad = reach_word._pad_pow2(R_100K)
    NW = reach_word.n_words(M)
    _compile(reach_word._jitted_walk_words(), one_chip,
             ((S, O1), jnp.int32), ((S, NW), jnp.uint32),
             ((n_pad,), jnp.int32), ((n_pad, W), jnp.int32))
