"""Described-chip compiles: the kernels of the main path, at the sizes
the chip runs them, handed to the TPU's own compiler for a chip that is
DESCRIBED (``v5e:2x2``) and not attached.

What interpret mode cannot show, this does: a slice the tiling refuses,
a kernel over its fast-memory budget, a program that does not fit the
device, a sharded step the partitioner rejects.  Nothing runs, so it
says nothing about results or times -- ``chip_smoke.py`` on the chip
does that.  Each case lowers the jitted step the production factories
build, for ``ShapeDtypeStruct`` arguments placed on a described device,
compiles it, and checks that the kernel is in the program
(``tpu_custom_call``) and that the program fits one v5e chip.

The topology is described inside a module-scoped fixture of THIS file,
never at import: only one process may load the TPU's library, every
xdist worker imports every test file, and ``--dist loadfile`` gives
this file to one worker.  All described-chip compiles live in this one
file for the same reason.

Several eligibility predicates ask ``jax.default_backend()`` and would
take their CPU branch here; the ``as_tpu`` fixture steers them, in the
test, not through an option of the program.

Tier-1 cases take a few seconds each.  The long ones are marked
``slow``: they ran once while PR 21 was written (CHANGES.md has the
results) and run again whenever someone changes those kernels.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from dprf_tpu import get_engine  # noqa: E402
from dprf_tpu.generators.mask import MaskGenerator  # noqa: E402

#: one v5e chip's HBM; a program whose arguments + temporaries +
#: outputs exceed it cannot run there
V5E_HBM_BYTES = 16 * 1024 ** 3
#: the production lane counts (BASELINE.json configs 1 and 2)
BATCH = 1 << 22
SUB = 128          # production tile; conftest pins 32 for interpret


# ---------------------------------------------------------------------------
# fixtures: the described topology, built only once a test here runs

@pytest.fixture(scope="module")
def topo():
    """``v5e:2x2`` as the installed toolchain describes it, with the
    persistent compile cache off around the module (a described-chip
    executable is written to the cache but cannot be read back without
    a chip, so the next run would warn and compile again anyway)."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import (
        compilation_cache as cc)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 -- no libtpu / no lock
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from dprf_tpu.parallel.mesh import make_mesh
    assert len(topo.devices) == 4
    return make_mesh(4, devices=list(topo.devices))


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the eligibility predicates that ask jax.default_backend()
    onto their TPU branch (sha256 / keccak / ext / rules kernels are
    TPU-only because XLA:CPU cannot compile their unrolled graphs)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(sharding, shape=(), dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(lowered, kernels: int = 1):
    """Compile for the described chip; the kernel must be in the
    program and the program must fit one chip.  Returns (compiled,
    optimized-HLO text)."""
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= kernels, \
        "no Mosaic kernel in the compiled program"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
    return compiled, text


def _md5_target(word: bytes):
    return get_engine("md5", device="cpu").parse_target(
        hashlib.md5(word).hexdigest())


def _mask_worker(engine_name: str, mask: str, targets, batch=BATCH):
    """The production kernel worker (interpret=False), unwarmed: its
    constructor builds the jitted step and runs nothing."""
    from dprf_tpu.runtime.worker import PallasMaskWorker
    return PallasMaskWorker(
        get_engine(engine_name, device="jax"), MaskGenerator(mask),
        targets, batch=batch, hit_capacity=64,
        oracle=get_engine(engine_name, device="cpu"),
        interpret=False, sub=SUB)


def _ntlm_1k_targets():
    """Config 2's list: 1,000 uniform (so unmatchable) NTLM digests,
    the list `dprf bench --config 2` runs."""
    from dprf_tpu.bench import uniform_digest_lines
    cpu = get_engine("ntlm", device="cpu")
    return [cpu.parse_target(line)
            for line in uniform_digest_lines(1000, 16)]


# ---------------------------------------------------------------------------
# tier-1: the kernels chip_smoke.py's md5-mask / ntlm-1k / serve / bcrypt
# phases reach, at their production sizes

def test_device_kind_is_in_the_roofline_table(topo):
    """The described chip is the chip telemetry/perf.py has a band
    for: the table's key is the string JAX reports, not a guess."""
    from dprf_tpu.telemetry import perf
    kinds = {d.device_kind for d in topo.devices}
    assert kinds == {"TPU v5 lite"}
    assert kinds <= set(perf.CHIP_INT_OPS_BANDS)
    assert {d.platform for d in topo.devices} == {"tpu"}


def test_md5_mask_kernel_4m_lanes(one_chip):
    """BASELINE config 1's per-batch step: 4,194,304 lanes, tile
    128 x 128, single target."""
    w = _mask_worker("md5", "?l?l?l?l?l?l", [_md5_target(b"zzzzzz")])
    assert w.stride == BATCH and not w._interpret
    _compile(w.step.lower(_sds(one_chip, (6,)), _sds(one_chip)))


@pytest.mark.parametrize("inner", [16, 64])
def test_md5_loop_superstep(one_chip, inner):
    """Config 1's DEFAULT fused dispatch (SUPER_MODE == "loop"): a
    fori_loop over one offset-aware compiled kernel.  inner 64 is what
    a 308,915,776-candidate sweep at this batch runs."""
    from dprf_tpu.ops.superstep import make_loop_super_step
    w = _mask_worker("md5", "?l?l?l?l?l?l", [_md5_target(b"zzzzzz")])
    assert w.SUPER_MODE == "loop"
    step, groups = w._make_loop_parts(inner)
    ls = make_loop_super_step(step, inner, w._super_batch(), groups)
    _, text = _compile(ls.lower(_sds(one_chip, (6,)), _sds(one_chip)))
    assert "while" in text          # one kernel in a loop, not unrolled
    assert text.count("tpu_custom_call") < inner


def test_sha256_mask_kernel(one_chip, as_tpu):
    """The statically unrolled SHA-256 kernel (TPU-only: XLA:CPU
    cannot compile it, so no interpret-mode test ever ran it)."""
    cpu = get_engine("sha256", device="cpu")
    t = cpu.parse_target(hashlib.sha256(b"zzzzzz").hexdigest())
    w = _mask_worker("sha256", "?l?l?l?l?l?l", [t])
    _compile(w.step.lower(_sds(one_chip, (6,)), _sds(one_chip)))


def test_ntlm_1k_targets_kernel(one_chip):
    """Config 2's per-batch step: NTLM (MD4 over UTF-16LE), ?a x 7,
    1,000 targets through the in-kernel probe bitmap."""
    w = _mask_worker("ntlm", "?a?a?a?a?a?a?a", _ntlm_1k_targets())
    assert w.multi
    _compile(w.step.lower(_sds(one_chip, (7,)), _sds(one_chip)))


def test_ntlm_1k_loop_superstep(one_chip):
    """Config 2's fused dispatch: the multi-target loop program with
    its two accumulation groups (maybe lanes, collided tiles)."""
    from dprf_tpu.ops.superstep import make_loop_super_step
    w = _mask_worker("ntlm", "?a?a?a?a?a?a?a", _ntlm_1k_targets())
    step, groups = w._make_loop_parts(64)
    assert len(groups) == 2
    ls = make_loop_super_step(step, 64, w._super_batch(), groups)
    _compile(ls.lower(_sds(one_chip, (7,)), _sds(one_chip)))


def test_ntlm_1k_tile_reprobe_has_no_custom_call(one_chip):
    """Config 2's collided-tile re-probe at the production tile: the
    kernel's body as plain XLA.  The benchmark's trace reads every
    custom call of a program as the hash kernel at `--batch` lanes,
    and the TPU compiler wraps an XLA gather's indices in one, so the
    re-probe must compile to none."""
    w = _mask_worker("ntlm", "?a?a?a?a?a?a?a", _ntlm_1k_targets())
    _, text = _compile(
        w._reprobe.lower(_sds(one_chip, (7,)), _sds(one_chip)), kernels=0)
    assert "custom-call" not in text and "gather(" not in text


def test_ntlm_bulk_list_loop_superstep(one_chip):
    """A bulk list's fused dispatch (ISSUE 29): the digest kernel in a
    loop of 64 with the probe stage behind it, for 100,000 targets.
    The table is three ARGUMENTS of the program (bitmap, sorted
    digests, their first words), the kernel is in it once and under
    its own name, which is what the benchmark's trace matches."""
    from dprf_tpu.bench import uniform_digest_lines
    from dprf_tpu.ops.pallas_mask import DIGEST_KERNEL_NAME
    from dprf_tpu.ops.superstep import make_loop_super_step
    cpu = get_engine("ntlm", device="cpu")
    w = _mask_worker("ntlm", "?a?a?a?a?a?a?a",
                     [cpu.parse_target(line)
                      for line in uniform_digest_lines(100_000, 16)])
    assert w.probe_table is not None and w._reprobe is None
    table = tuple(_sds(one_chip, a.shape, a.dtype)
                  for a in w._table_args)
    step, groups = w._make_loop_parts(64)
    ls = make_loop_super_step(step, 64, w._super_batch(), groups)
    compiled, text = _compile(
        ls.lower(_sds(one_chip, (7,)), _sds(one_chip), *table))
    assert text.count("tpu_custom_call") == 1
    assert f"%{DIGEST_KERNEL_NAME}." in text
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args >= w.probe_table.nbytes


def test_nested_1k_targets_kernel(one_chip, as_tpu):
    """The pallas_ext multi-target step (md5(md5($p)), 1,000 uniform
    targets): the same in-kernel probe bitmap as the CORES kernels."""
    from dprf_tpu.bench import uniform_digest_lines
    from dprf_tpu.ops.pallas_ext import make_ext_multi_crack_step
    tw = np.stack([np.frombuffer(bytes.fromhex(h), "<u4").astype(np.uint32)
                   for h in uniform_digest_lines(1000, 16)])
    step = make_ext_multi_crack_step(
        "md5(md5)", MaskGenerator("?l?l?l?l?l?l"), tw, 1 << 20, 64)
    _compile(step.lower(_sds(one_chip, (6,)), _sds(one_chip)))


def test_bcrypt_eks_advance_512(one_chip):
    """Config 4's cost-loop kernel at batch 512 (4 KB of S-box state
    per candidate in VMEM)."""
    from dprf_tpu.ops.pallas_bcrypt import make_pallas_eks_advance
    adv = make_pallas_eks_advance(512)
    u32 = jnp.uint32
    _compile(adv.lower(_sds(one_chip, (512, 18), u32),
                       _sds(one_chip, (512, 1024), u32),
                       _sds(one_chip, (512, 18), u32),
                       _sds(one_chip, (18,), u32), _sds(one_chip)))


def test_pmkid_kernel_step_is_one_program_for_every_target(one_chip):
    """Config 5's kernel worker (`?d` x 8, batch 32,768): what it
    dispatches for two targets of one ESSID length lowers to the same
    program, so the second target's compile is the first one's
    persistent-cache entry.  A target's ESSID, MACs or digest baked in
    as a constant would make a program, and half a minute of Mosaic
    compile, a target.  Lowering only: nothing is compiled."""
    from dprf_tpu.engines.device.pmkid import PallasPmkidWorker
    eng = get_engine("wpa2-pmkid", device="jax")
    cpu = get_engine("wpa2-pmkid", device="cpu")
    gen = MaskGenerator("?d" * 8)
    texts = []
    for line in ("00112233445566778899aabbccddeeff*0a1b2c3d4e5f*"
                 "a0b1c2d3e4f5*" + b"net-1a2b3c4d".hex(),
                 "ffeeddccbbaa99887766554433221100*020000000001*"
                 "0c0000000002*" + b"HomeNet-2.4G".hex()):
        w = PallasPmkidWorker(eng, gen, [cpu.parse_target(line)],
                              batch=1 << 15, hit_capacity=64, oracle=cpu)
        ((n, *targs),) = w._targs
        args = (jnp.asarray(gen.digits(0), jnp.int32), jnp.int32(0),
                jnp.int32(eng.iterations), *targs)
        texts.append(w._steps[n].lower(
            *[_sds(one_chip, np.shape(a), a.dtype) for a in args]).as_text())
    assert "tpu_custom_call" in texts[0]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("inner", [1, 16])
def test_sharded_kernel_step_on_four_described_chips(mesh4, inner):
    """`dprf crack --devices 4` on config 2: the fused kernel as the
    per-shard compute of the shard_map runtime, on a Mesh of the four
    described devices.  The partitioner must accept it, every device
    must get its own kernel and buffers, and the ONE collective round
    must be in the program."""
    from dprf_tpu.parallel.sharded import make_sharded_kernel_mask_step
    twords = np.stack([np.frombuffer(t.digest, dtype="<u4")
                       .astype(np.uint32) for t in _ntlm_1k_targets()])
    step = make_sharded_kernel_mask_step(
        "ntlm", MaskGenerator("?a?a?a?a?a?a?a"), twords, mesh4,
        BATCH, hit_capacity=64, sub=SUB, interpret=False)
    assert step.n_devices == 4 and step.super_span == 4 * BATCH
    rep = NamedSharding(mesh4, P())
    program = step if inner == 1 else step.superstep(inner)
    compiled, text = _compile(
        program.lower(_sds(rep, (7,)), _sds(rep)))
    # the psum and the three all_gathers of the runtime come out of
    # the compiler as ONE combined all-reduce: one collective round
    # per dispatch, whatever inner is
    assert text.count(" all-reduce(") == 1
    assert "all-gather" not in text and "all-to-all" not in text
    # each device finds its own shard of the window
    assert "replica-id" in text or "partition-id" in text
    # one program per device of the mesh
    assert len(compiled.input_shardings[0][0].device_set) == 4


# ---------------------------------------------------------------------------
# slow: the long compiles chip_smoke.py also reaches, and the kernels no
# chip run of this PR covers

@pytest.mark.slow
def test_rules_kernel_sha256_best64_1m_words(one_chip, as_tpu):
    """Config 3 at its stated size: 2^20 words x best64 through the
    rule-interpreter kernel, per-batch and wide (its fused shape).
    About a minute of Mosaic compile: one kernel per rule-depth
    bucket."""
    from dprf_tpu.bench import _synthetic_words
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import load_rules
    from dprf_tpu.runtime.worker import PallasWordlistWorker
    gen = WordlistRulesGenerator(_synthetic_words(1 << 20),
                                 load_rules("best64"), max_len=24)
    cpu = get_engine("sha256", device="cpu")
    t = cpu.parse_target("ff" * 32)
    w = PallasWordlistWorker(get_engine("sha256", device="jax"), gen,
                             [t], batch=1 << 18, hit_capacity=64,
                             oracle=cpu, interpret=False)
    assert w.SUPER_MODE == "wide"

    def lower(step):
        # every argument described, the 84 MB word table included
        return step.lower(
            _sds(one_chip), _sds(one_chip), _sds(one_chip, (8,)),
            _sds(one_chip, step.words4.shape, step.words4.dtype),
            _sds(one_chip, step.lens3.shape, step.lens3.dtype))

    from dprf_tpu.ops.pallas_rules import step_buckets
    kernels = len(step_buckets(gen.rules))    # one per rule depth
    assert kernels >= 2
    _compile(lower(w.step), kernels=kernels)
    _compile(lower(w._wide_step(8 * w.word_batch)), kernels=kernels)


@pytest.mark.slow
def test_pmkid_kernel(one_chip):
    """Config 5: PBKDF2-HMAC-SHA1 x 4096 -> PMKID, 8-char lowercase
    passphrases.  About half a minute of Mosaic compile (14 unrolled
    SHA-1 compressions per iteration)."""
    from dprf_tpu.ops.pallas_pbkdf2 import make_pmkid_kernel_step
    gen = MaskGenerator("?l?l?l?l?l?l?l?l")
    step = make_pmkid_kernel_step(gen, 1 << 15, 8, hit_capacity=64)
    s = one_chip
    _compile(step.lower(_sds(s, (8,)), _sds(s), _sds(s), _sds(s, (8,)),
                        _sds(s, (5,)), _sds(s, (4,))))


def _lower_keccak(s):
    from dprf_tpu.ops.pallas_keccak import make_pallas_keccak_crack_step
    eng = get_engine("sha3-256", device="jax")
    gen = MaskGenerator("?l?l?l?l?l?l")
    tw = np.frombuffer(b"\xff" * 32, ">u4").astype(np.uint32)
    step = make_pallas_keccak_crack_step(gen, tw, 1 << 20, eng._pad_byte,
                                         eng._rate, eng.digest_size)
    return step.lower(_sds(s, (6,)), _sds(s))


def _lower_nested(s):
    from dprf_tpu.ops.pallas_ext import make_ext_mask_crack_step
    gen = MaskGenerator("?l?l?l?l?l?l")
    tw = np.frombuffer(b"\xff" * 16, "<u4").astype(np.uint32)
    step = make_ext_mask_crack_step("md5(md5)", gen, tw, 1 << 20, 64)
    return step.lower(_sds(s, (6,)), _sds(s))


def _lower_salted(s):
    from dprf_tpu.ops.pallas_ext import make_salted_crack_step
    gen = MaskGenerator("?l?l?l?l?l?l")
    step = make_salted_crack_step("md5", "ps", gen, 1 << 20, 8, 64)
    return step.lower(_sds(s, (6,)), _sds(s), _sds(s, (8,)),
                      _sds(s, (4,)))


def _lower_krb5(s):
    from dprf_tpu.ops import pallas_krb5
    gen = MaskGenerator("?l?l?l?l?l?l")
    tile = pallas_krb5.SUBC * pallas_krb5.CHUNKS
    step = pallas_krb5.make_krb5_crack_step(gen, 8 * tile, 64)
    cpu = get_engine("krb5tgs", device="cpu")
    t = cpu.parse_target(
        "$krb5tgs$23$*u$R$s*$" + "11" * 16 + "$" + "22" * 64)
    targs = pallas_krb5.target_scalars(t)
    return step.lower(_sds(s, (6,)), _sds(s),
                      *[jax.ShapeDtypeStruct(np.shape(a), np.asarray(
                          a).dtype, sharding=s) for a in targs])


def _lower_pdf(s, key_len):
    from dprf_tpu.ops import pallas_krb5, pallas_pdf
    gen = MaskGenerator("?l?l?l?l?l?l")
    tile = pallas_krb5.SUBC * pallas_pdf.CHUNKS
    step = pallas_pdf.make_pdf_crack_step(gen, 4 * tile, 3, key_len,
                                          hit_capacity=64)
    return step.lower(_sds(s, (6,)), _sds(s), _sds(s, (8,)),
                      _sds(s, (16,)), _sds(s, (4,)), _sds(s, (4,)))


def _lower_krb5aes_kdf(s):
    from dprf_tpu.ops.pallas_pbkdf2 import make_pbkdf2_kdf_pallas_fn
    gen = MaskGenerator("?l?l?l?l?l?l")
    fn = jax.jit(make_pbkdf2_kdf_pallas_fn(gen, SUB * 128, 16, 8,
                                           sub=SUB))
    return fn.lower(_sds(s, (6,)), _sds(s, (1,)), _sds(s, (16,)))


def _lower_7z(s):
    from dprf_tpu.ops.pallas_7z import make_7z_kdf_pallas_fn
    gen = MaskGenerator("?l?l?l?l?l?l")
    fn = make_7z_kdf_pallas_fn(gen, batch=1 << 13, salt=b"",
                               cycles=19)
    return fn.lower(_sds(s, (6,)))


@pytest.mark.slow
@pytest.mark.parametrize("name,lower", [
    ("keccak", _lower_keccak),
    ("nested-md5md5", _lower_nested),
    ("salted-md5-ps", _lower_salted),
    ("krb5-rc4", _lower_krb5),
    ("pdf-r3-k16", lambda s: _lower_pdf(s, 16)),
    ("pdf-r3-k5", lambda s: _lower_pdf(s, 5)),
    ("krb5aes-pbkdf2", _lower_krb5aes_kdf),
    ("7z-kdf", _lower_7z),
], ids=lambda v: v if isinstance(v, str) else "")
def test_other_kernels_compile(one_chip, as_tpu, monkeypatch, name,
                               lower):
    """The kernel families no chip run of this PR reaches: does the
    installed Mosaic accept them at all?  (A compile that passes is
    not a chip run; their gates -- DPRF_PDF_K5_KERNEL,
    DPRF_KRB5AES_KERNEL -- stay as they are until one has run.)"""
    monkeypatch.setenv("DPRF_PDF_K5_KERNEL", "1")
    _compile(lower(one_chip))
