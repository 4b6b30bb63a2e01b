"""AOT scale proofs: compile the big configs against virtual TPU topologies.

Single-chip CI cannot run Llama-3-8B serving or 70B FSDP training, but it
CAN prove they compile, shard, and fit: JAX ahead-of-time compilation
(``jit(...).lower(...).compile()``) against a compile-only TPU topology
(``jax.experimental.topologies``) runs the real XLA:TPU compiler for the
target slice shape — no TPU hardware attached — and
``compiled.memory_analysis()`` reports the per-chip HBM the SPMD program
needs. This is the scale-validation role the reference delegates to real
cluster runs (BASELINE.md rows 4–5: 8B serving on v5p, 70B FSDP on
v5p-128 multi-slice; SURVEY.md §7 step 7).

Proofs ship as a CLI (``python -m kubeflow_tpu.parallel.aot`` /
``make scale-proof``) and bench.py folds the numbers into BENCH extra so
every round records them.

HBM budgets are per-chip device memory: v5p = 95 GB, v5e = 16 GB.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeflow_tpu.parallel import sharding as shd

HBM_PER_CHIP_GB = {"v5p": 95.0, "v5e": 16.0, "v4": 32.0}

# per-chip peak (bf16 FLOP/s, HBM bytes/s) — the public generation table
# used for the compiler-level roofline estimate (no hardware attached)
CHIP_SPECS = {
    "v5p": (459e12, 2765e9),
    "v5e": (197e12, 819e9),
    "v4": (275e12, 1228e9),
}

# aggregate per-chip interconnect bandwidth, bytes/s. ICI: the public
# per-chip figures (v5p 4,800 Gbps, v5e 1,600 Gbps, v4 2,400 Gbps). DCN
# (multi-slice, per chip): a stated planning assumption — data-center
# fabric per v5p host is ~100-200 Gbps shared by 4 chips; 25 GB/s/chip is
# deliberately optimistic-but-plausible and is named in est_basis so the
# projection's weakest input is visible, not buried.
ICI_BW_PER_CHIP = {"v5p": 600e9, "v5e": 200e9, "v4": 300e9}
DCN_BW_PER_CHIP = 25e9
# fraction of collective time assumed hidden under compute (XLA overlaps
# FSDP all-gathers with the matmuls that consume them; latency-bound
# tails and the last layer's collectives are not hideable)
COLLECTIVE_OVERLAP = 0.75


@dataclasses.dataclass
class ScaleProof:
    name: str
    topology: str
    num_slices: int
    n_devices: int
    mesh_axes: dict[str, int]
    argument_gb: float          # resident state (params/opt/cache) per chip
    temp_gb: float              # transient activations per chip
    output_gb: float
    peak_gb: float              # argument + temp + output - aliased
    hbm_gb: float               # chip budget
    fits: bool
    flops_per_step: float = 0.0
    # scale estimates (training proofs only), recorded with their basis:
    # - est_step_floor_s: the hard compute-bound floor for the per-chip
    #   program, max(flops, HLO-reported flops)/peak. XLA:TPU
    #   cost_analysis() does NOT multiply loop (scan) bodies by trip
    #   count, so its flop/byte counts are floored by the analytic model
    #   flops; when HLO flops exceed the floor (remat recompute captured)
    #   they are used.
    # - est_mfu: projection = the measured single-chip MFU of the SAME
    #   trainer recipe (from the latest BENCH artifact — see
    #   measured_single_chip_mfu) scaled by the config's remat recompute
    #   factor (dots ~1.0, full ~0.75: one extra forward of ~2ND per
    #   6ND), then derated by the exposed-collective bubble: per-chip
    #   all-gather/reduce-scatter/all-reduce wire bytes (max of the
    #   HLO-parsed ops and the analytic FSDP floor) over ICI/DCN
    #   bandwidth, COLLECTIVE_OVERLAP assumed hidden under compute.
    #   est_mfu is a projection, not a measurement.
    est_step_floor_s: float = 0.0
    est_mfu: float = 0.0
    est_step_s: float = 0.0            # compute projection + exposed comms
    est_tokens_per_sec_per_chip: float = 0.0
    est_basis: str = ""
    # collective model (training proofs): per-chip wire bytes per step =
    # max(HLO-parsed collective ops, analytic FSDP floor), split by the
    # fabric they traverse; coll_bubble_s is the part NOT hidden under
    # compute (COLLECTIVE_OVERLAP), already folded into est_step_s
    coll_ici_gb: float = 0.0
    coll_dcn_gb: float = 0.0
    coll_s: float = 0.0
    coll_bubble_s: float = 0.0
    # est_mfu restated against the BASELINE >=0.40 target (>1 = margin)
    margin_vs_target: float = 0.0
    # MPMD pipeline projection (filled when the bench hands a MEASURED
    # interleaved bubble to scale_proofs): the measured bubble rescaled
    # to the target stage/microbatch/virtual-stage shape by the ratio of
    # analytic fill/drain bounds, then folded into est_mfu
    pipe_bubble_measured: float = 0.0
    pipe_bubble_projected: float = 0.0
    pipe_mfu: float = 0.0
    pipe_basis: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def topology_devices(topology: str, num_slices: int = 1):
    """Compile-only devices for e.g. ``v5p:4x4x4`` (64 chips) — the real
    XLA:TPU target, no hardware needed."""
    from jax.experimental import topologies

    kwargs = {"num_slices": num_slices} if num_slices > 1 else {}
    return list(topologies.get_topology_desc(topology, "tpu", **kwargs).devices)


def _sds(shape_tree, sharding_tree):
    """ShapeDtypeStructs with shardings — AOT inputs, no arrays."""
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree, sharding_tree,
    )


@contextlib.contextmanager
def _backend_is(platform: str):
    """``jax.default_backend()`` answers ``platform`` inside: for code that
    picks a path by the backend while it is lowered for described devices."""
    real = jax.default_backend
    jax.default_backend = lambda: platform
    try:
        yield
    finally:
        jax.default_backend = real


def _analyze(name, topology, num_slices, mesh, compiled,
             hbm_gb, flops=0.0) -> ScaleProof:
    m = compiled.memory_analysis()
    gb = 1 << 30
    arg = m.argument_size_in_bytes / gb
    temp = m.temp_size_in_bytes / gb
    out = m.output_size_in_bytes / gb
    alias = m.alias_size_in_bytes / gb
    peak = arg + temp + out - alias
    return ScaleProof(
        name=name, topology=topology, num_slices=num_slices,
        n_devices=mesh.devices.size,
        mesh_axes={k: v for k, v in mesh.shape.items() if v > 1},
        argument_gb=round(arg, 3), temp_gb=round(temp, 3),
        output_gb=round(out, 3), peak_gb=round(peak, 3),
        hbm_gb=hbm_gb, fits=peak < hbm_gb, flops_per_step=flops,
    )


# ------------------------------------------------------------- training --

def aot_train_proof(
    cfg: llama.LlamaConfig,
    mesh_config: MeshConfig,
    topology: str,
    *,
    num_slices: int = 1,
    batch: int = 64,
    seq: int = 8192,
    name: str = "train",
    hbm_gb: Optional[float] = None,
    depot=None,
    measured_overlap: Optional[float] = None,
    overlap_src: str = "",
) -> ScaleProof:
    """Compile the FULL train step (fwd+bwd+adam, grad-accum off) for the
    target topology and report per-chip HBM. Uses the production Trainer —
    the same step the JAXJob worker runs — so the proof covers the real
    remat/sharding choices, not a stand-in.

    ``depot``: an executable depot (``parallel/depot.py``) to publish the
    compiled step to — the operator-ahead-of-submit form of compile-once:
    run the proof before the job and gang workers whose program,
    topology and toolchain fingerprint-match fetch instead of compiling.
    (Entries are platform-keyed; serialize failures degrade to a counted
    plain compile, like every depot path.)"""
    from kubeflow_tpu.training import Trainer, TrainerConfig, lm_loss_fn

    devices = topology_devices(topology, num_slices)
    mesh = build_mesh(mesh_config, devices=devices)
    trainer = Trainer(
        mesh=mesh,
        init_params_fn=lambda rng: llama.init_params(
            rng, cfg, dtype=cfg.dtype),
        params_logical_axes=llama.param_logical_axes(cfg),
        loss_fn=lm_loss_fn(llama.forward, cfg),
        config=TrainerConfig(learning_rate=1e-4),
    )
    params_shape = jax.eval_shape(
        lambda rng: llama.init_params(rng, cfg, dtype=cfg.dtype),
        jax.random.key(0))
    opt_shape = jax.eval_shape(trainer.optimizer.init, params_shape)
    params_in = _sds(params_shape, trainer.param_shardings)
    opt_in = _sds(opt_shape, trainer.opt_shardings)
    # [batch, seq+1]: the lm_loss batch contract every worker lowers
    # (inputs tokens[:, :-1], targets [:, 1:]) — the model really runs
    # on ``seq`` tokens, matching the flops accounting below, and the
    # depot fingerprint matches what a gang worker of this config
    # computes (the ahead-of-submit publish would never hit otherwise)
    batch_in = {"tokens": jax.ShapeDtypeStruct(
        (batch, seq + 1), jnp.int32, sharding=trainer.batch_sharding)}
    lowered = trainer.lower_step(params_in, opt_in, batch_in)
    if depot is not None:
        from kubeflow_tpu.parallel.depot import load_or_compile

        compiled, _ = load_or_compile(lowered, depot, mesh=mesh)
    else:
        compiled = lowered.compile()
    flops = cfg.flops_per_token(seq) * batch * seq
    kind = topology.split(":", 1)[0]
    param_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(params_shape))
    proof = _analyze(name, topology, num_slices, mesh, compiled,
                     hbm_gb or HBM_PER_CHIP_GB.get(kind, 95.0), flops)
    _estimate_roofline(proof, compiled, kind, flops, batch * seq,
                       getattr(cfg, "remat", None),
                       param_bytes=param_bytes,
                       measured_overlap=measured_overlap,
                       overlap_src=overlap_src)
    return proof


#  fallback only — the projection prefers the LATEST bench artifact (see
# measured_single_chip_mfu); this constant is the round-4-era measurement
# kept for environments with no BENCH_r*.json next to the repo
MEASURED_SINGLE_CHIP_MFU = 0.587   # Llama-1B, remat=dots + pallas, v5e
_REMAT_MFU_FACTOR = {"dots": 1.0, "full": 0.75, "none": 1.0, None: 1.0}


def measured_single_chip_mfu(root: Optional[str] = None) -> tuple[float, str]:
    """(mfu, provenance) from the newest ``BENCH_r*.json`` driver
    artifact, so the projection tracks what the bench ACTUALLY measured
    instead of a baked constant that drifts (VERDICT Weak #3).

    Artifacts carry either a ``parsed`` copy of the bench line or only a
    truncated ``tail`` — both are tried (newest round first); anything
    unreadable, or an mfu outside (0, 1], falls through. Search root:
    ``KFT_BENCH_DIR`` env, else the repo root this package sits in."""
    root = root or os.environ.get("KFT_BENCH_DIR") or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    def round_no(path: str) -> int:
        # numeric, not lexicographic: r100 > r99, unpadded r9 stays r9
        m = re.search(r"r(\d+)", os.path.basename(path))
        return int(m.group(1)) if m else -1

    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                       key=round_no, reverse=True):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        mfu = None
        try:
            mfu = float(doc["parsed"]["extra"]["mfu"])
        except (KeyError, TypeError, ValueError):
            m = re.search(r'"mfu":\s*([0-9.eE+-]+)', doc.get("tail") or "")
            if m:
                try:
                    mfu = float(m.group(1))
                except ValueError:
                    mfu = None
        if mfu is not None and 0.0 < mfu <= 1.0:
            return mfu, os.path.basename(path)
    return MEASURED_SINGLE_CHIP_MFU, "baked-in fallback (no bench artifact)"


# ------------------------------------------------- collective modeling --

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](T\()?")
# the RESULT-shape region between `=` and the op call: instruction NAMES
# also contain the op string (%all-reduce.2 = f32[] all-reduce(...)), so
# anchoring on `=` is what keeps the shape parse on the right side;
# -start async halves carry the groups/shape, -done halves match nothing
_COLL_LINE_RE = re.compile(
    r"=\s*(?P<lhs>.*?)\s*"
    r"(?P<op>all-gather|reduce-scatter|all-reduce|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def hlo_collective_bytes(hlo_text: str, devices_per_slice: int,
                         n_devices: int = 0) -> dict:
    """Per-chip wire bytes of every collective in an HLO module, split by
    the fabric it crosses (a replica group whose members span slices
    rides DCN). Wire-byte model per participant of a g-way group moving a
    B-byte result: all-gather B*(g-1)/g, reduce-scatter B*(g-1) (result
    is the shard), all-reduce 2B*(g-1)/g, all-to-all B*(g-1)/g,
    collective-permute B. An op with EMPTY or absent replica_groups
    spans all participants (XLA's all-devices spelling) — ``n_devices``
    sets its group size so those ops aren't silently dropped.

    CAVEAT (same one the flops floor documents): XLA HLO text does NOT
    multiply scan/while bodies by trip count, so collectives inside a
    scanned layer stack appear ONCE — callers take max() with the
    analytic model below rather than trusting this parse alone."""
    ici = dcn = 0.0
    ops = 0
    for line in hlo_text.splitlines():
        m_op = _COLL_LINE_RE.search(line)
        if m_op is None:
            continue
        op = m_op.group("op")
        shapes = _SHAPE_RE.findall(m_op.group("lhs"))
        if not shapes:
            continue
        payload = max(_shape_bytes(d, s) for d, s in shapes)
        # default: empty/absent replica_groups = ONE group of every
        # participant, the all-devices spelling some channel-based ops
        # use — not a droppable parse failure
        g = max(n_devices, 1)
        crosses = g > max(devices_per_slice, 1)
        m = _GROUPS_RE.search(line)
        if m:
            groups = [
                [int(x) for x in grp.split(",") if x.strip()]
                for grp in re.findall(r"\{([0-9,\s]*)\}", m.group(0))]
            groups = [grp for grp in groups if grp]
            if groups:
                g = max(len(grp) for grp in groups)
                crosses = any(
                    len({i // max(devices_per_slice, 1) for i in grp}) > 1
                    for grp in groups)
        else:
            m = _IOTA_RE.search(line)
            if m:
                g = int(m.group(2))
                # iota-with-transpose = strided groups: the multi-slice
                # mesh puts the slice axis outermost, so strided groups
                # are the ones that cross it
                crosses = bool(m.group(4)) or g > devices_per_slice
        if g <= 1:
            continue
        if op == "all-gather":
            wire = payload * (g - 1) / g
        elif op == "reduce-scatter":
            wire = payload * (g - 1)
        elif op == "all-reduce":
            wire = 2 * payload * (g - 1) / g
        elif op == "all-to-all":
            wire = payload * (g - 1) / g
        else:
            wire = payload
        ops += 1
        if crosses:
            dcn += wire
        else:
            ici += wire
    return {"ici_bytes": ici, "dcn_bytes": dcn, "ops": ops}


def analytic_fsdp_collective_bytes(param_bytes: int,
                                   mesh_axes: dict) -> dict:
    """The analytic floor the HLO parse is max'ed with: per training step
    an FSDP-sharded model all-gathers its parameters twice (forward +
    re-gather in backward) and reduce-scatters gradients once over the
    fsdp axis (ICI), then all-reduces the resulting grad SHARD across the
    dcn_data axis (DCN). Per-chip wire bytes, dtypes as stored."""
    f = int(mesh_axes.get("fsdp", 1))
    d = int(mesh_axes.get("dcn_data", 1))
    ici = 3.0 * param_bytes * (f - 1) / f if f > 1 else 0.0
    shard = param_bytes / max(f, 1)
    dcn = 2.0 * shard * (d - 1) / d if d > 1 else 0.0
    return {"ici_bytes": ici, "dcn_bytes": dcn}


def _estimate_roofline(proof: ScaleProof, compiled, kind: str,
                       model_flops: float, tokens: int,
                       remat: Optional[str],
                       param_bytes: int = 0,
                       measured_overlap: Optional[float] = None,
                       overlap_src: str = "") -> None:
    """Fill the est_* fields (see ScaleProof docstring for the basis).

    ``measured_overlap`` replaces the COLLECTIVE_OVERLAP assumption with
    a MEASURED DCN/compute overlap fraction (the MPMD pipeline bench's
    ``dcn_overlap_fraction`` — a real async transport hiding real wire
    time under real compute on this rig); est_basis then says
    "measured" instead of "assumed", naming ``overlap_src``."""
    peak, _bw = CHIP_SPECS.get(kind, CHIP_SPECS["v5p"])
    n = proof.n_devices
    hlo_flops = 0.0
    hlo_text = ""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        hlo_flops = float(ca.get("flops", 0.0))
    except Exception:
        pass
    try:
        hlo_text = compiled.as_text()
    except Exception:
        pass
    per_chip_flops = max(hlo_flops, model_flops / n)
    proof.est_step_floor_s = round(per_chip_flops / peak, 4)
    mfu_meas, mfu_src = measured_single_chip_mfu()
    mfu = mfu_meas * _REMAT_MFU_FACTOR.get(remat, 1.0)
    compute_s = model_flops / n / peak / mfu

    # collectives: per-chip wire bytes per step = max(what the compiled
    # HLO actually contains, the analytic FSDP floor) per fabric — the
    # HLO parse counts scan bodies once (like the flops floor), the
    # analytic model can't see TP/unexpected collectives; max() is the
    # honest combination of two under-counts
    per_slice = max(1, n // max(proof.num_slices, 1))
    parsed = (hlo_collective_bytes(hlo_text, per_slice, n_devices=n)
              if hlo_text else
              {"ici_bytes": 0.0, "dcn_bytes": 0.0, "ops": 0})
    if proof.num_slices <= 1:
        # single slice: nothing crosses DCN by definition — fold any
        # strided groups the iota heuristic flagged back into ICI
        parsed["ici_bytes"] += parsed["dcn_bytes"]
        parsed["dcn_bytes"] = 0.0
    analytic = analytic_fsdp_collective_bytes(param_bytes, proof.mesh_axes)
    ici = max(parsed["ici_bytes"], analytic["ici_bytes"])
    dcn = max(parsed["dcn_bytes"], analytic["dcn_bytes"])
    ici_bw = ICI_BW_PER_CHIP.get(kind, ICI_BW_PER_CHIP["v5p"])
    coll_s = ici / ici_bw + dcn / DCN_BW_PER_CHIP
    # at most COLLECTIVE_OVERLAP of the collective time hides under
    # compute, and hiding is additionally capped by the compute that
    # exists to hide it: exposed bubble = coll - min(o*coll, compute).
    # The (1-o)*coll floor keeps the derate honest even in the
    # compute-bound regime (latency tails and the last layer's
    # collectives never overlap), so the collectives fold into
    # est_step_s/est_mfu non-vacuously.
    overlap = (measured_overlap if measured_overlap is not None
               else COLLECTIVE_OVERLAP)
    bubble = coll_s - min(overlap * coll_s, compute_s)
    t = compute_s + bubble

    proof.coll_ici_gb = round(ici / (1 << 30), 3)
    proof.coll_dcn_gb = round(dcn / (1 << 30), 3)
    proof.coll_s = round(coll_s, 4)
    proof.coll_bubble_s = round(bubble, 4)
    proof.est_mfu = round(model_flops / n / peak / t, 4)
    proof.est_step_s = round(t, 4)
    proof.est_tokens_per_sec_per_chip = round(tokens / t / n, 1)
    proof.margin_vs_target = round(proof.est_mfu / 0.40, 3)
    proof.est_basis = (
        f"projection: measured {mfu_meas} single-chip MFU ({mfu_src}, "
        "same trainer recipe) x remat factor "
        f"{_REMAT_MFU_FACTOR.get(remat, 1.0)}; "
        "compute floor from max(model, HLO) flops / peak "
        "(XLA:TPU cost_analysis omits scan trip counts); "
        "collectives modeled: max(HLO-parsed, analytic FSDP) wire bytes "
        f"— {parsed['ops']} HLO collective ops, scan bodies counted once "
        f"— over ICI {ici_bw / 1e9:.0f} GB/s/chip + DCN "
        f"{DCN_BW_PER_CHIP / 1e9:.0f} GB/s/chip, "
        + (f"{overlap:.0%} measured compute-overlapped "
           f"({overlap_src or 'MPMD pipeline bench'}); "
           if measured_overlap is not None
           else f"{COLLECTIVE_OVERLAP:.0%} assumed compute-overlapped; ")
        + "est_mfu restated vs the 0.40 target as margin_vs_target")


# ------------------------------------------------- pipeline projection --

def pipeline_mfu_projection(measured_bubble: float, *,
                            n_stages: int, microbatches: int,
                            virtual_stages: int = 1,
                            target_stages: int = 8,
                            target_microbatches: int = 64,
                            target_virtual_stages: Optional[int] = None
                            ) -> float:
    """Rescale a MEASURED pipeline bubble to a target shape — pure python.

    The measured bubble (MPMD bench, real transport + real compute)
    carries the rig's scheduling overhead ON TOP of the analytic
    fill/drain bound; the target shape changes only the analytic part.
    Projection = measured × analytic(target) / analytic(measured), which
    preserves the measured overhead RATIO rather than assuming the
    target magically hits the ideal bound. Falls back to the raw
    measurement when the measured shape has no analytic bubble (S=1)."""
    from kubeflow_tpu.parallel.mpmd import analytic_bubble_bound

    meas_bound = analytic_bubble_bound(n_stages, microbatches,
                                       virtual_stages)
    tgt_bound = analytic_bubble_bound(
        target_stages, target_microbatches,
        virtual_stages if target_virtual_stages is None
        else target_virtual_stages)
    if meas_bound <= 0.0:
        return measured_bubble
    return measured_bubble * tgt_bound / meas_bound


def apply_pipeline_projection(proof: ScaleProof, bubble: dict) -> None:
    """Fold a measured interleaved-1F1B bubble into a training proof.

    ``bubble`` is the bench's measurement record: ``bubble_fraction`` +
    the (n_stages, microbatches, virtual_stages) shape it was measured
    at (+ optional ``src``). The v5p-128 target shape is the ROADMAP
    north star: 8 stages x 16 chips, interleaved."""
    measured = float(bubble["bubble_fraction"])
    s = int(bubble.get("n_stages", 2))
    m = int(bubble.get("microbatches", 8))
    v = int(bubble.get("virtual_stages", 1))
    tgt_v = int(bubble.get("target_virtual_stages", max(v, 2)))
    tgt_m = int(bubble.get("target_microbatches", 64))
    projected = pipeline_mfu_projection(
        measured, n_stages=s, microbatches=m, virtual_stages=v,
        target_stages=8, target_microbatches=tgt_m,
        target_virtual_stages=tgt_v)
    proof.pipe_bubble_measured = round(measured, 4)
    proof.pipe_bubble_projected = round(projected, 4)
    proof.pipe_mfu = round(proof.est_mfu * (1.0 - projected), 4)
    proof.pipe_basis = (
        f"measured interleaved bubble {measured:.4f} at "
        f"S={s} M={m} V={v} ({bubble.get('src', 'MPMD pipeline bench')}) "
        f"rescaled by analytic(S=8, M={tgt_m}, V={tgt_v}) / "
        f"analytic(measured shape) -> {projected:.4f}; pipe_mfu = "
        "est_mfu x (1 - projected bubble) for the 8-stage x 16-chip "
        "v5p-128 pipeline shape")


# -------------------------------------------------------------- serving --

def aot_serve_proof(
    cfg: llama.LlamaConfig,
    topology: str,
    *,
    tensor: int,
    batch: int = 8,
    max_seq: int = 8192,
    prefill_len: int = 2048,
    name: str = "serve",
    hbm_gb: Optional[float] = None,
) -> ScaleProof:
    """Compile the two programs a tensor-parallel ``LLMEngine`` spends its
    time in, as it builds them, for the target slice: ``paged_decode_step``
    (the kernel the engine resolves on a TPU, shard_map'd over the mesh)
    over the dense arena's worth of blocks sharded on the kv-head dim, and
    the model's bucket prefill. Per-chip HBM must hold bf16 params/TP + the
    KV pool/TP beside either program."""
    from kubeflow_tpu.serving import paged_kv

    devices = topology_devices(topology)
    mesh = build_mesh(MeshConfig(tensor=tensor), devices=devices)
    param_sh = shd.tree_shardings(mesh, llama.param_logical_axes(cfg))
    params_shape = jax.eval_shape(
        lambda rng: llama.init_params(rng, cfg, dtype=cfg.dtype),
        jax.random.key(0))
    params_in = _sds(params_shape, param_sh)

    repl = NamedSharding(mesh, PartitionSpec())
    kv_sh = NamedSharding(
        mesh, PartitionSpec(None, None, None, "tensor", None))
    block_size = 64                       # the engine's default
    table_len = max_seq // block_size
    cache_shape = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, batch, max_seq, block_size, batch * table_len + 1))
    cache_in = _sds(cache_shape, {"k": kv_sh, "v": kv_sh, "len": repl})

    def replicated(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)

    kernel, _ = paged_kv.resolve_decode_kernel(
        "auto", mesh=mesh, n_kv_heads=cfg.n_kv_heads, platform="tpu")
    decode = jax.jit(
        lambda p, t, c, tables: paged_kv.paged_decode_step(
            p, t, cfg, c, tables, kernel=kernel, mesh=mesh),
        donate_argnums=(2,))
    # the program asks the backend whether to interpret its kernel: this
    # process holds no chip, the answer is the target's
    with _backend_is("tpu"):
        compiled_decode = decode.lower(
            params_in, replicated((batch,)), cache_in,
            replicated((batch, table_len))).compile()

    ops = cfg.paged_ops()
    prefill = jax.jit(
        lambda p, toks, lens: ops.bucket_prefill(p, toks, lens))
    compiled_prefill = prefill.lower(
        params_in, replicated((batch, prefill_len)),
        replicated((batch,))).compile()

    kind = topology.split(":", 1)[0]
    budget = hbm_gb or HBM_PER_CHIP_GB.get(kind, 95.0)
    proof_d = _analyze(f"{name}-decode", topology, 1, mesh,
                       compiled_decode, budget)
    proof_p = _analyze(f"{name}-prefill", topology, 1, mesh,
                       compiled_prefill, budget)
    # the pool stays resident while a prefill runs, though that program
    # takes no pool: count its shard among the prefill's arguments
    pool_gb = sum(
        x.size * x.dtype.itemsize
        for x in (cache_shape["k"], cache_shape["v"])) / tensor / (1 << 30)
    proof_p.argument_gb = round(proof_p.argument_gb + pool_gb, 3)
    proof_p.peak_gb = round(proof_p.peak_gb + pool_gb, 3)
    proof_p.fits = proof_p.peak_gb < budget
    # one resident footprint serves both programs; report the worse one
    worst = max((proof_d, proof_p), key=lambda p: p.peak_gb)
    worst.name = name
    return worst


# ------------------------------------------------------------- the bar --

def scale_proofs(quick: bool = False,
                 measured_overlap: Optional[float] = None,
                 overlap_src: str = "",
                 measured_bubble: Optional[dict] = None) -> list[ScaleProof]:
    """The BASELINE.md ladder rows single-chip CI can't run:

    - row 4: Llama-3-8B serving on a v5p-8 (4-chip) slice, TP=4;
    - row 5: Llama-3-70B FSDP training on v5p-128 (64 chips), TWO slices
      joined over DCN (dcn_data=2 × fsdp=32) — the multi-slice shape.
    """
    # persistent compile cache: the three proofs cost ~12 min of XLA:TPU
    # compile cold; a later run on the same machine reuses what it can
    from kubeflow_tpu.utils import compile_cache

    compile_cache.ensure()

    out = []
    out.append(aot_serve_proof(
        llama.llama3_8b(), "v5p:2x2x1", tensor=4,
        batch=8, max_seq=8192, name="llama3_8b-serve-v5p8"))
    if not quick:
        # row 1 (north-star #1): the flagship 8B TRAINING config at its
        # real scale — FSDP over a v5p-16 slice, the same remat/attention
        # choices the single-chip bench runs
        out.append(aot_train_proof(
            llama.llama3_8b(remat="dots", attn_impl="pallas",
                            attn_block=512),
            MeshConfig(fsdp=8),
            "v5p:2x2x2",
            batch=16, seq=8192, name="llama3_8b-train-v5p16",
            measured_overlap=measured_overlap, overlap_src=overlap_src))
        out.append(aot_train_proof(
            llama.llama3_70b(remat="full", attn_impl="pallas", attn_block=256),
            MeshConfig(dcn_data=2, fsdp=32),
            "v5p:4x4x2", num_slices=2,
            batch=64, seq=8192, name="llama3_70b-fsdp-v5p128",
            measured_overlap=measured_overlap, overlap_src=overlap_src))
        if measured_bubble is not None:
            # re-derive the v5p-128 MFU projection from the MEASURED
            # interleaved bubble (8 stages x 16 chips is the pipeline
            # decomposition of the same 64-chip 2-slice shape)
            apply_pipeline_projection(out[-1], measured_bubble)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="kubeflow_tpu.parallel.aot")
    ap.add_argument("--quick", action="store_true",
                    help="8B serving proof only (70B compile is slower)")
    args = ap.parse_args(argv)
    ok = True
    for proof in scale_proofs(quick=args.quick):
        print(json.dumps(proof.to_dict()))
        ok = ok and proof.fits
    if not ok:
        print("SCALE PROOF FAILED: peak per-chip HBM exceeds budget")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
