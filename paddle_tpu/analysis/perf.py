"""Static cost model: FLOPs / bytes / roofline time per op, per layer,
per program — without compiling anything.

The performance half of the analysis substrate (the verifier + lint are
the correctness half): walk every block over the recorded shape/dtype
metadata the verifier already validates, assign each op FLOPs and bytes
moved from a per-op-type estimator registry, and convert both into a
roofline-bound time estimate for a parameterized chip (peak FLOP/s +
HBM bandwidth).  This is the estimate-and-rank front-end the ROADMAP's
compile-and-time autotuner prunes candidates with (TVM/Ansor-style:
never compile what the cost model can already reject), and the engine
behind the perf lint rules (perf_rules.py) and `tools/program_cost.py`.

Model assumptions (documented; see README "Performance analysis"):
  * FLOP counts mirror XLA's HLO cost analysis conventions — matmul
    2*M*N*K, conv 2*out*K_h*K_w*C_in/groups, elementwise 1/element,
    transcendentals (exp/tanh/erf/...) tracked separately and NOT
    counted as FLOPs.  Anchored by a validation harness
    (`validate_cost_model`) against `xla_cost.cost_of_jitted` over the
    model zoo.
  * Bytes are per-op operand+result traffic: the model assumes NO
    cross-op fusion, so byte totals upper-bound what fused XLA moves.
    Time estimates therefore rank programs (fewer ops / fused ops win);
    they are not wall-clock predictions.
  * Dynamic (-1) dims are substituted with `dynamic_dim` (default 8).
  * time(op) = max(flops/peak_flops, bytes/hbm_bw); whichever term wins
    labels the op compute- or memory-bound (the roofline).
"""

from __future__ import annotations

from ..observability.xla_cost import CHIP_PEAKS as _CHIP_PEAKS
from . import opgraph

__all__ = [
    "ChipSpec",
    "CostReport",
    "DecodeStepCost",
    "OpCost",
    "PipelineRanking",
    "decode_step_cost",
    "program_cost",
    "op_cost_types",
    "register_op_cost",
    "rank_pass_pipelines",
    "validate_cost_model",
    "xla_cost_of_program",
]

DEFAULT_DYNAMIC_DIM = 8

# MXU/VPU tiling constants for one TPU core: (sublane, lane) — an operand
# tile is [8, 128] and the MXU contracts 128x128.  Used by utilization
# estimates (tiny-matmul lint) and padded-shape math.
MXU_SUBLANE = 8
MXU_LANE = 128


class ChipSpec:
    """Roofline parameters for one chip: peak FLOP/s + HBM bytes/s +
    ICI bytes/s (the collective-traffic axis, `analysis.comm`) + host
    link bytes/s (the host-embedding exchange axis,
    `fluid.host_embedding`).

    `detect` resolves every axis through `observability.xla_cost`
    (explicit arg > env override > `CHIP_PEAKS[device_kind]`).  This is
    a static model, so on a host with no accelerator it prices the chip
    the repo targets (`V5E`) and says so in the spec's name; an
    accelerator `CHIP_PEAKS` does not list is an error, never priced as
    a v5e."""

    def __init__(self, name, peak_flops, hbm_bw, ici_bw=None,
                 host_bw=None):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.ici_bw = float(ici_bw) if ici_bw else None
        self.host_bw = float(host_bw) if host_bw else None

    @classmethod
    def detect(cls, peak_flops=None, hbm_bw=None, device_kind=None,
               ici_bw=None, host_bw=None):
        from ..observability import xla_cost

        row = xla_cost.chip_peaks(device_kind)
        if row is None:                 # host CPU: price the target chip
            device_kind, name = V5E_DEVICE_KIND, V5E.name + " (target)"
        else:
            name = row["name"]
        return cls(
            name,
            xla_cost.peak_flops(peak_flops, device_kind),
            xla_cost.hbm_bandwidth(hbm_bw, device_kind),
            xla_cost.ici_bandwidth(ici_bw, device_kind),
            xla_cost.host_bandwidth(host_bw, device_kind))

    def to_dict(self):
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "ici_bw": self.ici_bw,
                "host_bw": self.host_bw}

    def __repr__(self):
        return "ChipSpec(%s, %.0f GFLOP/s, %.0f GB/s, ICI %s, host %s)" % (
            self.name, self.peak_flops / 1e9, self.hbm_bw / 1e9,
            "%.0f GB/s" % (self.ici_bw / 1e9) if self.ici_bw else "n/a",
            "%.0f GB/s" % (self.host_bw / 1e9) if self.host_bw else "n/a")


# the chip the repo targets; its figures live in `xla_cost.CHIP_PEAKS`
V5E_DEVICE_KIND = "TPU v5 lite"
V5E = ChipSpec(**_CHIP_PEAKS[V5E_DEVICE_KIND])


# ---------------------------------------------------------------------------
# per-op-type FLOP estimators
# ---------------------------------------------------------------------------
#
# An estimator sees resolved shapes and returns {"flops": float,
# "transcendentals": float (optional), "bytes": float (optional override)}.
# Anything unregistered defaults to elementwise: 1 FLOP per output
# element (XLA's convention for add/mul/compare/select/...).

_COST_REGISTRY: dict = {}
_WARNED_ESTIMATORS: set = set()

# pure data movement / indexing: 0 FLOPs, bytes still move
_MOVEMENT_OPS = {
    "reshape2", "squeeze2", "unsqueeze2", "flatten2",
    "flatten_contiguous_range", "transpose", "transpose2", "cast",
    "concat", "split", "slice", "strided_slice", "stack", "unstack",
    "gather", "gather_nd", "one_hot", "expand",
    "expand_v2", "expand_as", "broadcast_to", "tile", "pad", "pad2d",
    "pad3d", "pad_constant_like", "assign", "shape", "fill_constant",
    "fill_constant_batch_size_like", "fill_any_like", "fill_zeros_like",
    "fill_zeros_like2", "arange", "range", "reverse", "roll", "flip",
    "feed", "fetch", "index_select", "sequence_unpad", "lod_reset",
    "tril_triu", "tril", "triu", "unbind", "eye", "linspace",
    "meshgrid", "diag", "diag_v2", "diag_embed", "diagonal", "crop",
    "crop_tensor",
}

# ops whose core work is a transcendental per element (XLA tracks these
# outside "flops")
_TRANSCENDENTAL_OPS = {
    "exp", "tanh", "sigmoid", "log", "sqrt", "rsqrt", "erf", "sin",
    "cos", "softplus", "logsigmoid", "mish", "silu",
}


class OpCost:
    """One op's estimated cost (flops/bytes/comm/host/time) + location.

    ``comm_bytes`` is per-chip WIRE traffic of a collective op (ring
    factors, `analysis.comm`); ``host_bytes`` is host-link traffic of a
    host-resident exchange (the distributed-embedding pull/push —
    `fluid.host_embedding`).  The roofline is the four-way
    max(flops/peak, hbm/bw, wire/ici, host/host_bw); a dominated op is
    labeled ``bound="comm"`` / ``bound="host"`` accordingly."""

    __slots__ = ("block_idx", "op_idx", "op_type", "flops",
                 "transcendentals", "bytes", "comm_bytes", "host_bytes",
                 "time_s", "bound", "provenance")

    def __init__(self, block_idx, op_idx, op_type, flops, transcendentals,
                 nbytes, chip, provenance=(), comm_bytes=0.0,
                 host_bytes=0.0):
        self.block_idx = block_idx
        self.op_idx = op_idx
        self.op_type = op_type
        self.flops = float(flops)
        self.transcendentals = float(transcendentals)
        self.bytes = float(nbytes)
        self.comm_bytes = float(comm_bytes or 0.0)
        self.host_bytes = float(host_bytes or 0.0)
        t_compute = self.flops / chip.peak_flops
        t_memory = self.bytes / chip.hbm_bw
        t_comm = (self.comm_bytes / chip.ici_bw
                  if self.comm_bytes and chip.ici_bw else 0.0)
        t_host = (self.host_bytes / chip.host_bw
                  if self.host_bytes and chip.host_bw else 0.0)
        self.time_s = max(t_compute, t_memory, t_comm, t_host)
        if t_host and t_host >= max(t_compute, t_memory, t_comm):
            self.bound = "host"
        elif t_comm and t_comm >= t_compute and t_comm >= t_memory:
            self.bound = "comm"
        else:
            self.bound = "compute" if t_compute >= t_memory else "memory"
        self.provenance = list(provenance or ())

    def to_dict(self):
        return {
            "block_idx": self.block_idx, "op_idx": self.op_idx,
            "op_type": self.op_type, "flops": self.flops,
            "transcendentals": self.transcendentals, "bytes": self.bytes,
            "comm_bytes": self.comm_bytes,
            "host_bytes": self.host_bytes,
            "time_s": self.time_s, "bound": self.bound,
            "provenance": list(self.provenance),
        }


def register_op_cost(*types):
    """Decorator: register a FLOP estimator for one or more op types.

    Estimator signature::

        def est(ins, outs, attrs):  # -> {"flops": float, ...}

    where ins/outs are {slot: [(shape, dtype_str), ...]} with dynamic
    dims already substituted."""
    def deco(fn):
        for t in types:
            _COST_REGISTRY[t] = fn
        return fn
    return deco


def op_cost_types():
    """Op types with a dedicated (non-default) estimator."""
    return sorted(_COST_REGISTRY)


def _elems(shape):
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _first(slots, name):
    vals = slots.get(name)
    return vals[0] if vals else None


def _out_elems(outs):
    return max((_elems(s) for s, _dt in
                (v for vs in outs.values() for v in vs)), default=0)


@register_op_cost("matmul")
def _cost_matmul(ins, outs, attrs):
    x = _first(ins, "X")
    out = _first(outs, "Out")
    if x is None or out is None:
        return {"flops": 0}
    xs = x[0]
    tx = attrs.get("transpose_X", attrs.get("transpose_x", False))
    k = xs[-2] if (tx and len(xs) > 1) else xs[-1]
    return {"flops": 2.0 * _elems(out[0]) * int(k)}


@register_op_cost("mul")
def _cost_mul(ins, outs, attrs):
    x = _first(ins, "X")
    out = _first(outs, "Out")
    if x is None or out is None:
        return {"flops": 0}
    num_col = int(attrs.get("x_num_col_dims", 1))
    k = _elems(x[0][num_col:])
    return {"flops": 2.0 * _elems(out[0]) * k}


@register_op_cost("bmm", "addmm", "bilinear_tensor_product", "mv", "dot")
def _cost_bmm(ins, outs, attrs):
    x = _first(ins, "X")
    out = _first(outs, "Out")
    if x is None or out is None:
        return {"flops": 0}
    k = x[0][-1] if x[0] else 1
    return {"flops": 2.0 * max(_elems(out[0]), 1) * int(k)}


def _conv_overlap_sum(I, O, K, stride, pad_lo, dilation):
    """Sum over output positions of how many kernel taps land inside
    the input (XLA's cost analysis counts only these valid MACs — on a
    1x1 map a padded 3x3 kernel does 1 MAC, not 9)."""
    total = 0
    for o in range(O):
        start = o * stride - pad_lo
        for k in range(K):
            if 0 <= start + k * dilation < I:
                total += 1
    return total


def _conv_geometry(ins, outs, attrs):
    """(in_spatial, out_spatial, batch) honoring data_format."""
    x = _first(ins, "Input") or _first(ins, "X")
    out = _first(outs, "Output") or _first(outs, "Out")
    if x is None or out is None:
        return None
    fmt = attrs.get("data_format", attrs.get("data_layout", "NCHW"))
    xs, os_ = x[0], out[0]
    if fmt.endswith("C"):   # NHWC / NDHWC
        return xs[1:-1], os_[1:-1], os_[0]
    return xs[2:], os_[2:], os_[0]


@register_op_cost("conv2d", "depthwise_conv2d", "conv3d")
def _cost_conv(ins, outs, attrs):
    w = _first(ins, "Filter")
    geo = _conv_geometry(ins, outs, attrs)
    if w is None or geo is None:
        return {"flops": 0}
    in_sp, out_sp, batch = geo
    ws = w[0]  # OIHW: [C_out, C_in/groups, *kernel]
    c_out, c_in_g = ws[0], ws[1]
    kernel = ws[2:]
    nd = len(kernel)
    strides = list(attrs.get("strides", [1] * nd)) or [1] * nd
    dils = list(attrs.get("dilations", [1] * nd)) or [1] * nd
    pads = list(attrs.get("paddings", [0] * nd))
    if len(pads) == nd:           # symmetric per dim
        lo = pads
    elif len(pads) == 2 * nd:     # [lo, hi] pairs
        lo = pads[0::2]
    else:
        lo = [0] * nd
    macs = 1.0
    for d in range(min(nd, len(in_sp), len(out_sp))):
        macs *= _conv_overlap_sum(int(in_sp[d]), int(out_sp[d]),
                                  int(kernel[d]), int(strides[d]),
                                  int(lo[d]), int(dils[d]))
    return {"flops": 2.0 * int(batch) * int(c_out) * int(c_in_g) * macs}


@register_op_cost("conv2d_transpose", "conv3d_transpose",
                  "deformable_conv", "deformable_conv_v1")
def _cost_conv_transpose(ins, outs, attrs):
    w = _first(ins, "Filter")
    out = _first(outs, "Output") or _first(outs, "Out")
    if w is None or out is None:
        return {"flops": 0}
    ws = w[0]
    return {"flops": 2.0 * _elems(out[0]) * _elems(ws[1:])}


@register_op_cost("pool2d", "pool3d", "max_pool2d_with_index",
                  "max_pool3d_with_index")
def _cost_pool(ins, outs, attrs):
    out = _first(outs, "Out")
    if out is None:
        return {"flops": 0}
    win = _elems(attrs.get("ksize", attrs.get("kernel_size", [1])))
    if attrs.get("global_pooling"):
        x = _first(ins, "X")
        if x is not None and len(x[0]) >= 3:
            win = _elems(x[0][2:])
    return {"flops": max(win - 1, 0) * _elems(out[0])}


@register_op_cost("softmax", "log_softmax", "sequence_softmax")
def _cost_softmax(ins, outs, attrs):
    x = _first(ins, "X") or _first(ins, "Logits")
    if x is None:
        return {"flops": 0}
    n = _elems(x[0])
    return {"flops": 4.0 * n, "transcendentals": float(n)}


@register_op_cost("softmax_with_cross_entropy")
def _cost_softmax_xent(ins, outs, attrs):
    # calibrated vs XLA: log-softmax + label select/NLL ~= 8 FLOP and
    # 2 transcendentals per logit
    x = _first(ins, "Logits") or _first(ins, "X")
    if x is None:
        return {"flops": 0}
    n = _elems(x[0])
    return {"flops": 8.0 * n, "transcendentals": 2.0 * n}


@register_op_cost("cross_entropy", "cross_entropy2")
def _cost_xent(ins, outs, attrs):
    x = _first(ins, "X")
    if x is None:
        return {"flops": 0}
    n = _elems(x[0])
    return {"flops": float(n), "transcendentals": float(n)}


@register_op_cost("lookup_table", "lookup_table_v2")
def _cost_lookup(ins, outs, attrs):
    # XLA bills the gather's address math ~1 FLOP per fetched element
    c = {"flops": float(_out_elems(outs))}
    if attrs.get("is_distributed"):
        # host-RAM table (fluid.host_embedding): every step the touched
        # rows cross the host link twice (pull values + push gradients)
        # with their ids.  The static bound bills one row per looked-up
        # id (no np.unique dedup — the same upper-bound convention as
        # the no-fusion byte model; the measured dedup lives in the
        # hostemb_unique_ratio metric).
        ids = _first(ins, "Ids")
        w = _first(ins, "W")
        if ids is not None and w is not None:
            n_ids = float(_elems(ids[0]))
            row_bytes = int(w[0][-1]) * _itemsize(w[1])
            # pull row + push f32 grad row + 8-byte id each way
            c["host_bytes"] = n_ids * (row_bytes + int(w[0][-1]) * 4
                                       + 2 * 8)
    return c


@register_op_cost("flash_attention")
def _cost_flash_attention(ins, outs, attrs):
    q, k = _first(ins, "Q"), _first(ins, "K")
    if q is None or k is None:
        return {"flops": 0}
    qs, ks = q[0], k[0]
    if len(qs) != 4 or len(ks) != 4:
        return {"flops": 0}
    if attrs.get("layout", "BHSD") == "BSHD":
        b, sq, h, d = qs
        sk = ks[1]
    else:
        b, h, sq, d = qs
        sk = ks[2]
    scores = float(b) * h * sq * sk
    # QK^T + PV matmuls (2*d MACs each per score) + softmax/scale/mask
    # (calibrated ~9/score vs the naive-composition HLO)
    return {"flops": 4.0 * scores * d + 9.0 * scores,
            "transcendentals": scores}


@register_op_cost("batch_norm", "sync_batch_norm")
def _cost_batch_norm(ins, outs, attrs):
    x = _first(ins, "X")
    if x is None:
        return {"flops": 0}
    # calibrated vs XLA: normalize+scale+shift ~= 4 FLOP/element
    return {"flops": 4.0 * _elems(x[0])}


@register_op_cost("fused_batch_norm_act")
def _cost_fused_bn_act(ins, outs, attrs):
    x = _first(ins, "X")
    if x is None:
        return {"flops": 0}
    return {"flops": 5.0 * _elems(x[0])}   # batch_norm + 1/elem epilogue


@register_op_cost("layer_norm", "group_norm", "instance_norm", "data_norm")
def _cost_layer_norm(ins, outs, attrs):
    x = _first(ins, "X")
    if x is None:
        return {"flops": 0}
    # calibrated vs XLA: mean/var reductions + normalize + affine
    # ~= 8 FLOP/element
    return {"flops": 8.0 * _elems(x[0])}


@register_op_cost("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                  "reduce_prod", "sum", "mean", "logsumexp",
                  "frobenius_norm", "squared_l2_norm", "p_norm")
def _cost_reduce(ins, outs, attrs):
    n = max((_elems(s) for s, _dt in
             (v for vs in ins.values() for v in vs)), default=0)
    return {"flops": float(n)}


@register_op_cost("dropout")
def _cost_dropout(ins, outs, attrs):
    x = _first(ins, "X")
    if x is None:
        return {"flops": 0}
    if attrs.get("is_test"):
        return {"flops": float(_elems(x[0]))}
    return {"flops": 2.0 * _elems(x[0])}


@register_op_cost("gelu")
def _cost_gelu(ins, outs, attrs):
    x = _first(ins, "X")
    n = float(_elems(x[0])) if x else 0.0
    if attrs.get("approximate", False):
        # tanh form: ~8 cheap elementwise ops around one tanh
        return {"flops": 8.0 * n, "transcendentals": n}
    # exact (erf) form: XLA expands erf to a rational polynomial billed
    # as ~64 flops/element (calibrated against the HLO cost analysis)
    return {"flops": 64.0 * n, "transcendentals": n}


@register_op_cost("matmul_bias_act")
def _cost_matmul_bias_act(ins, outs, attrs):
    """Fused-epilogue GEMM: matmul FLOPs + one elementwise epilogue
    pass — and, critically, ONE [M,N] traffic pass instead of the
    unfused chain's three (matmul write + add read/write + act
    read/write).  The default bytes accounting (operand+result of THIS
    op only) models that exactly, which is what makes
    `rank_pass_pipelines` statically rank `matmul_bias_act_fuse` above
    the unfused baseline."""
    x = _first(ins, "X")
    out = _first(outs, "Out")
    if x is None or out is None:
        return {"flops": 0}
    xs = x[0]
    n_out = _elems(out[0])
    xn = attrs.get("x_num_col_dims")
    if xn is not None:
        k = _elems(xs[int(xn):])
    else:
        tx = attrs.get("transpose_X", attrs.get("transpose_x", False))
        k = xs[-2] if (tx and len(xs) > 1) else xs[-1]
    flops = 2.0 * n_out * int(k)
    act = attrs.get("act_type", "none")
    trans = 0.0
    if act == "gelu":
        # same per-element accounting as the standalone gelu estimator
        if attrs.get("approximate", False):
            flops += 8.0 * n_out
        else:
            flops += 64.0 * n_out
        trans += float(n_out)
    elif act == "tanh":
        trans += float(n_out)
    elif act == "relu":
        flops += float(n_out)
    if _first(ins, "Bias") is not None:
        flops += float(n_out)
    return {"flops": flops, "transcendentals": trans}


@register_op_cost("cond", "while_loop_op", "static_rnn",
                  "recompute_segment")
def _cost_container(ins, outs, attrs):
    # control-flow / recompute containers do no arithmetic themselves
    # and their slots alias inner-op tensors: the inner ops (walked
    # separately by program_cost) bill all flops and traffic
    return {"flops": 0, "bytes": 0.0}


@register_op_cost("switch_moe")
def _cost_switch_moe(ins, outs, attrs):
    x, gw = _first(ins, "X"), _first(ins, "GateW")
    w1 = _first(ins, "W1")
    if x is None or gw is None or w1 is None:
        return {"flops": 0}
    t, d = x[0]
    e = gw[0][1]
    h = w1[0][2]
    top_k = int(attrs.get("top_k", 1))
    cap = int(attrs.get("capacity_factor", 1.25) * top_k * t / e + 1)
    router = 2.0 * t * d * e + 4.0 * t * e        # gate matmul + softmax
    dispatch = 2.0 * t * e * cap * d * top_k      # "tec,td->ecd" einsums
    experts = 2.0 * e * cap * d * h * 2           # W1 and W2 matmuls
    combine = 2.0 * t * e * cap * d * top_k       # "tec,ecd->td" einsums
    return {"flops": router + dispatch + experts + combine
            + 8.0 * e * cap * h,                  # gelu epilogue
            "transcendentals": float(t * e + e * cap * h)}


_ITEMSIZES = {
    "bool": 1, "int8": 1, "uint8": 1, "float16": 2, "bfloat16": 2,
    "int16": 2, "int32": 4, "float32": 4, "int64": 8, "float64": 8,
    "complex64": 8, "complex128": 16,
}


def _itemsize(dtype):
    size = _ITEMSIZES.get(dtype)
    if size is not None:
        return size
    import numpy as np

    try:
        size = np.dtype(dtype.replace("bfloat16", "float16")).itemsize
    except TypeError:
        size = 4
    _ITEMSIZES[dtype] = size
    return size


def _default_cost(op_type, ins, outs, attrs):
    if op_type in _MOVEMENT_OPS:
        return {"flops": 0}
    n = _out_elems(outs)
    if op_type in _TRANSCENDENTAL_OPS:
        return {"flops": 0, "transcendentals": float(n)}
    return {"flops": float(n)}


# explicit collective ops (the c_* transpiler surface) -> comm kind.
# Priced per chip with the ring factors from `analysis.comm`; the group
# size comes from the op's ``nranks`` attr, falling back to the
# ``mesh_size`` a caller (tools/program_cost --mesh) provides.
_COLLECTIVE_OP_KINDS = {
    "c_allreduce_sum": "all-reduce",
    "c_allreduce_max": "all-reduce",
    "c_allreduce_min": "all-reduce",
    "c_allreduce_prod": "all-reduce",
    "c_broadcast": "broadcast",
    "c_allgather": "all-gather",
    "c_reducescatter": "reduce-scatter",
}


def _collective_comm_bytes(op_type, ins, outs, attrs, mesh_size):
    """Per-chip wire bytes of one c_* op (0 when the group is 1)."""
    from . import comm as comm_mod

    kind = _COLLECTIVE_OP_KINDS[op_type]
    n = int(attrs.get("nranks") or mesh_size or 1)
    if n <= 1:
        return 0.0
    # the billed buffer: input for reduce-style ops; OUTPUT for
    # all-gather (Out = nranks x X, the full payload) and for
    # reduce-scatter (Out is the shard, scaled by the "shard" factor)
    if kind == "all-gather":
        src, payload = outs, "full"
    elif kind == "reduce-scatter":
        src, payload = outs, "shard"
    else:
        src, payload = ins, "full"
    nbytes = sum(_elems(shape) * _itemsize(dtype)
                 for vals in src.values() for shape, dtype in vals)
    return comm_mod.collective_wire_bytes(kind, nbytes, n, payload=payload)


# ---------------------------------------------------------------------------
# program walk
# ---------------------------------------------------------------------------


def _resolve_shapes(program, bidx, op, dynamic_dim):
    """{slot: [(shape, dtype), ...]} for an op's inputs and outputs from
    recorded var metadata; -1 dims substituted with `dynamic_dim`.
    Returns (ins, outs, missing) — names with no recorded shape are
    listed in `missing` and skipped."""
    block = program.blocks[bidx]
    missing = []

    def slots(mapping):
        out = {}
        for slot, names in mapping.items():
            resolved = []
            for n in names:
                v = block._find_var_recursive(n)
                if v is None or v.shape is None:
                    missing.append(n)
                    continue
                shape = tuple(dynamic_dim if s == -1 else int(s)
                              for s in v.shape)
                resolved.append((shape, v.dtype))
            out[slot] = resolved
        return out

    return (slots(opgraph.op_inputs(op)), slots(opgraph.op_outputs(op)),
            missing)


def estimate_op_cost(program, bidx, oidx, op, chip,
                     dynamic_dim=DEFAULT_DYNAMIC_DIM, mesh_size=None):
    """OpCost for one op (real Operator or serialized sub-op dict).
    ``mesh_size`` is the collective group size used for c_* ops that
    carry no ``nranks`` attr (tools/program_cost --mesh)."""
    ins, outs, _missing = _resolve_shapes(program, bidx, op, dynamic_dim)
    op_type = opgraph.op_type(op)
    attrs = opgraph.op_attrs(op)
    est = _COST_REGISTRY.get(op_type)
    try:
        c = (est(ins, outs, attrs) if est
             else _default_cost(op_type, ins, outs, attrs))
    except Exception as e:
        # a broken estimator (typo'd slot in a user-registered one,
        # degenerate shapes) must not sink the report, but billing 0
        # silently would corrupt budgets/rankings without a signal
        if op_type not in _WARNED_ESTIMATORS:
            _WARNED_ESTIMATORS.add(op_type)
            import warnings

            warnings.warn(
                "cost estimator for op %r raised %s: %s — billing 0 "
                "FLOPs for every %r in this process" % (
                    op_type, type(e).__name__, e, op_type))
        c = {"flops": 0}
    nbytes = c.get("bytes")
    if nbytes is None:
        nbytes = 0.0
        for slots in (ins, outs):
            for vals in slots.values():
                for shape, dtype in vals:
                    nbytes += _elems(shape) * _itemsize(dtype)
    comm_bytes = c.get("comm_bytes", 0.0)
    if op_type in _COLLECTIVE_OP_KINDS:
        comm_bytes = _collective_comm_bytes(
            op_type, ins, outs, attrs, mesh_size)
    return OpCost(bidx, oidx, op_type, c.get("flops", 0.0),
                  c.get("transcendentals", 0.0), nbytes, chip,
                  provenance=opgraph.op_provenance(op),
                  comm_bytes=comm_bytes,
                  host_bytes=c.get("host_bytes", 0.0))


class CostReport:
    """Whole-program cost rollup: per-op entries + totals + groupings."""

    SCHEMA_VERSION = 1

    def __init__(self, entries, chip, dynamic_dim):
        self.entries = list(entries)
        self.chip = chip
        self.dynamic_dim = dynamic_dim

    # -- totals --------------------------------------------------------
    @property
    def total_flops(self):
        return sum(e.flops for e in self.entries)

    @property
    def total_transcendentals(self):
        return sum(e.transcendentals for e in self.entries)

    @property
    def total_bytes(self):
        return sum(e.bytes for e in self.entries)

    @property
    def total_comm_bytes(self):
        """Per-chip collective wire bytes (ring factors applied)."""
        return sum(e.comm_bytes for e in self.entries)

    @property
    def total_host_bytes(self):
        """Host-link exchange bytes (distributed-embedding pull/push)."""
        return sum(e.host_bytes for e in self.entries)

    @property
    def total_time_s(self):
        return sum(e.time_s for e in self.entries)

    @property
    def arithmetic_intensity(self):
        """FLOPs per byte moved — against chip.peak_flops/chip.hbm_bw
        (the roofline ridge) it says whether the program as a whole
        lives left (memory-bound) or right (compute-bound) of the ridge."""
        b = self.total_bytes
        return self.total_flops / b if b else 0.0

    # -- groupings -----------------------------------------------------
    def by_op_type(self):
        """[{op_type, count, flops, bytes, comm_bytes, time_s}] sorted
        by time desc."""
        groups = {}
        for e in self.entries:
            g = groups.setdefault(e.op_type, dict(
                op_type=e.op_type, count=0, flops=0.0, bytes=0.0,
                comm_bytes=0.0, host_bytes=0.0, time_s=0.0))
            g["count"] += 1
            g["flops"] += e.flops
            g["bytes"] += e.bytes
            g["comm_bytes"] += e.comm_bytes
            g["host_bytes"] += e.host_bytes
            g["time_s"] += e.time_s
        return sorted(groups.values(), key=lambda g: -g["time_s"])

    def by_layer(self):
        """Rollup keyed by the innermost provenance frame (the line of
        model code that built the op) when op-callstack capture was on;
        ops without provenance group under their op_type."""
        groups = {}
        for e in self.entries:
            key = e.provenance[0] if e.provenance else "<%s>" % e.op_type
            g = groups.setdefault(key, dict(
                layer=key, count=0, flops=0.0, bytes=0.0, time_s=0.0))
            g["count"] += 1
            g["flops"] += e.flops
            g["bytes"] += e.bytes
            g["time_s"] += e.time_s
        return sorted(groups.values(), key=lambda g: -g["time_s"])

    def dominant(self, n=10):
        """Top-n ops by estimated time."""
        return sorted(self.entries, key=lambda e: -e.time_s)[:n]

    # -- serialization -------------------------------------------------
    def to_dict(self, include_ops=True):
        d = {
            "schema_version": self.SCHEMA_VERSION,
            "chip": self.chip.to_dict(),
            "dynamic_dim": self.dynamic_dim,
            "totals": {
                "flops": self.total_flops,
                "transcendentals": self.total_transcendentals,
                "bytes": self.total_bytes,
                "comm_bytes": self.total_comm_bytes,
                "host_bytes": self.total_host_bytes,
                "time_s": self.total_time_s,
                "arithmetic_intensity": self.arithmetic_intensity,
                "op_count": len(self.entries),
            },
            "by_op_type": self.by_op_type(),
        }
        if include_ops:
            d["ops"] = [e.to_dict() for e in self.entries]
        return d

    def format(self, top=10):
        comm = self.total_comm_bytes
        host = self.total_host_bytes
        lines = [
            "program cost on %r: %.2f GFLOP, %.1f MB moved%s%s, "
            "est %.3f ms (%s-leaning, intensity %.1f FLOP/B)" % (
                self.chip.name, self.total_flops / 1e9,
                self.total_bytes / 1e6,
                ", %.2f MB collective wire" % (comm / 1e6) if comm else "",
                ", %.2f MB host exchange" % (host / 1e6) if host else "",
                self.total_time_s * 1e3,
                "compute" if self.arithmetic_intensity
                >= self.chip.peak_flops / self.chip.hbm_bw else "memory",
                self.arithmetic_intensity),
        ]
        for g in self.by_op_type()[:top]:
            extra = ""
            if g.get("comm_bytes"):
                extra += "  %.2f MB wire" % (g["comm_bytes"] / 1e6)
            if g.get("host_bytes"):
                extra += "  %.2f MB host" % (g["host_bytes"] / 1e6)
            lines.append(
                "  %-28s x%-4d %10.2f MFLOP %10.2f MB %8.1f us%s" % (
                    g["op_type"], g["count"], g["flops"] / 1e6,
                    g["bytes"] / 1e6, g["time_s"] * 1e6, extra))
        return "\n".join(lines)


def program_cost(program, chip=None, dynamic_dim=DEFAULT_DYNAMIC_DIM,
                 include_sub_ops=True, mesh_size=None):
    """Static CostReport over every real op in every block — so a cond
    bills BOTH branches (the static model cannot know which is taken)
    and a while bills ONE iteration of its body.  Containers (cond /
    while / static_rnn / recompute_segment) cost nothing themselves.

    Ops that control flow serializes into attrs are NOT re-counted when
    the container also anchors real sub-blocks (``sub_block*`` attrs —
    the dicts mirror ops already walked above); with `include_sub_ops`
    (default) attr-only sub-ops — recompute segments, whose ops exist
    NOWHERE else — are billed from the parent block's var metadata.

    ``mesh_size`` prices explicit c_* collective ops that carry no
    ``nranks`` attr (their wire bytes ride the ring factors against
    ``chip.ici_bw``); without it such ops cost no comm."""
    chip = chip or ChipSpec.detect()
    entries = []
    for bidx, oidx, op in opgraph.iter_all_ops(program):
        entries.append(
            estimate_op_cost(program, bidx, oidx, op, chip, dynamic_dim,
                             mesh_size=mesh_size))
        if include_sub_ops and not any(
                k.startswith("sub_block")
                for k in opgraph.op_attrs(op)):
            for sop in opgraph.iter_sub_ops(op):
                entries.append(estimate_op_cost(
                    program, bidx, oidx, sop, chip, dynamic_dim,
                    mesh_size=mesh_size))
    return CostReport(entries, chip, dynamic_dim)


# ---------------------------------------------------------------------------
# validation harness: static model vs XLA's own cost analysis
# ---------------------------------------------------------------------------


def _program_input_vars(program):
    """Vars block 0 execution needs as inputs (feeds + params + any
    var read before any op produces it), in first-use order."""
    block = program.global_block
    produced = set()
    inputs = []
    for op in block.ops:
        for n in op.all_input_names():
            if n in produced or n in inputs:
                continue
            v = block._find_var_recursive(n)
            if v is not None:
                inputs.append(n)
        produced.update(op.all_output_names())
    return inputs


def xla_cost_of_program(program, fetch_names,
                        dynamic_dim=DEFAULT_DYNAMIC_DIM):
    """Compile block 0 (is_test, zero-filled inputs) and return XLA's
    normalized `cost_analysis()` dict — the ground truth the static
    model is validated against.  None when the backend reports nothing
    (attribution is telemetry, never a failure source)."""
    import jax
    import numpy as np

    from ..fluid.core import dtypes as dtypes_mod
    from ..fluid.core.block_eval import run_ops
    from ..fluid.core.registry import LowerContext
    from ..observability import xla_cost

    block = program.global_block
    vals = {}
    for n in _program_input_vars(program):
        v = block._find_var_recursive(n)
        shape = tuple(dynamic_dim if s == -1 else int(s)
                      for s in (v.shape or ()))
        vals[n] = np.zeros(shape, dtype=np.dtype(
            dtypes_mod.to_jnp(v.dtype)))

    def f(env_in):
        env = dict(env_in)
        ctx = LowerContext(base_key=jax.random.PRNGKey(0), is_test=True)
        run_ops(block.ops, env, ctx)
        return [env[n] for n in fetch_names]

    return xla_cost.cost_of_jitted(jax.jit(f), vals)


def validate_cost_model(program, fetch_names, chip=None,
                        dynamic_dim=DEFAULT_DYNAMIC_DIM):
    """Compare static FLOPs against XLA cost analysis for block 0.

    Returns {"static_flops", "xla_flops", "rel_err"} or None when the
    backend reports no cost analysis.  The static side mirrors what the
    compiled executable contains: every real block-0 op plus the
    sub-ops serialized into its attrs (the exact dicts a cond/while/
    recompute lowering executes when block 0 is traced).  Best-effort
    caveat: serialized branch/body ops are billed only where their
    operand shapes resolve through block 0's var table, so programs
    whose control-flow bodies define private intermediate vars validate
    loosely — the anchored envelope is straight-line programs (the
    model zoo)."""
    xla = xla_cost_of_program(program, fetch_names,
                              dynamic_dim=dynamic_dim)
    if not xla or not xla.get("flops"):
        return None
    chip = chip or ChipSpec.detect()
    static = 0.0
    for oidx, op in enumerate(program.global_block.ops):
        static += estimate_op_cost(
            program, 0, oidx, op, chip, dynamic_dim).flops
        for sop in opgraph.iter_sub_ops(op):
            static += estimate_op_cost(
                program, 0, oidx, sop, chip, dynamic_dim).flops
    xf = float(xla["flops"])
    return {
        "static_flops": static,
        "xla_flops": xf,
        "rel_err": abs(static - xf) / xf if xf else 0.0,
    }


# ---------------------------------------------------------------------------
# pass-pipeline ranking: the autotuner's pruning front-end
# ---------------------------------------------------------------------------


class PipelineRanking:
    """One costed candidate: the pipeline (pass names) + its CostReport."""

    __slots__ = ("pipeline", "report", "error")

    def __init__(self, pipeline, report, error=None):
        self.pipeline = tuple(pipeline)
        self.report = report
        self.error = error

    @property
    def time_s(self):
        return self.report.total_time_s if self.report else float("inf")

    def to_dict(self):
        return {
            "pipeline": list(self.pipeline),
            "time_s": self.time_s if self.report else None,
            "flops": self.report.total_flops if self.report else None,
            "bytes": self.report.total_bytes if self.report else None,
            "error": self.error,
        }

    def __repr__(self):
        if self.report is None:
            return "PipelineRanking(%r, failed: %s)" % (
                list(self.pipeline), self.error)
        return "PipelineRanking(%r, est %.3f ms)" % (
            list(self.pipeline), self.time_s * 1e3)


def rank_pass_pipelines(program, candidates, chip=None,
                        dynamic_dim=DEFAULT_DYNAMIC_DIM, verify=True):
    """Statically cost pass-pipeline variants and order them fastest
    first — the pruning step before an autotuner compiles-and-times the
    survivors.

    Each candidate (an iterable of pass names / Pass instances, e.g.
    `[]` for the baseline or `["batch_norm_act_fuse"]`) runs on a CLONE
    via `ir.clone_and_apply(..., verify=verify)`; the original program
    is never mutated, and with verify=True a candidate whose pass breaks
    the program is excluded from the ranking (returned last, with the
    verification error recorded) instead of winning on a corrupt cost."""
    from ..fluid import ir

    chip = chip or ChipSpec.detect()
    ranked = []
    for cand in candidates:
        names = list(cand)
        try:
            clone = ir.clone_and_apply(program, names, verify=verify)
        except Exception as e:
            ranked.append(PipelineRanking(names, None, error=str(e)))
            continue
        ranked.append(PipelineRanking(
            names, program_cost(clone, chip=chip,
                                dynamic_dim=dynamic_dim)))
    return sorted(ranked, key=lambda r: r.time_s)


# ---------------------------------------------------------------------------
# autoregressive decode-step cost (paddle_tpu.generation)
# ---------------------------------------------------------------------------


class DecodeStepCost:
    """The decode step's roofline: one token per slot against a
    KV cache of L layers x ``[slots, cache_len, H*D]`` for K and for V.

    At batch 1-per-slot the MXU sees [slots, hidden] x [hidden, ...]
    matmuls — every weight byte and every cache byte is read for O(1)
    FLOPs per byte, so the step is **memory-bound** at any realistic
    slot count; the ceiling is HBM bandwidth, and tokens/s scales with
    how little you read per token.  That is the quantitative argument
    for the KV cache (read ``2*L*len*hidden`` bytes per token instead
    of recomputing ``O(len)`` positions) and for batching slots (the
    weight read amortizes across slots; the KV read does not).

    ``kv_read_bytes`` is per STEP (all slots); the per-token KV read is
    ``kv_read_bytes / slots``.  `tests/test_perf_gate.py` budgets it
    the way PR-13 gates collective bytes.

    ``tp > 1`` (`paddle_tpu.tp_serving`) adds the ICI leg: flops and
    HBM reads are PER CHIP (sharded weights and KV divide by ``tp``,
    replicated embeddings/LM head do not) and ``comm_bytes`` carries
    the per-chip all-reduce wire traffic — two ring all-reduces per
    layer over the ``[slots, hidden]`` activations — priced against
    ``chip.ici_bw``; ``bound`` can then be ``"ici"``."""

    __slots__ = ("slots", "cache_len", "flops", "kv_read_bytes",
                 "param_read_bytes", "bytes", "time_s", "bound",
                 "tokens_per_s", "chip", "paged", "block_size",
                 "kv_dtype_bytes", "tp", "comm_bytes")

    def __init__(self, slots, cache_len, flops, kv_read_bytes,
                 param_read_bytes, chip, paged=False, block_size=None,
                 kv_dtype_bytes=None, tp=1, comm_bytes=0.0):
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.flops = float(flops)
        self.kv_read_bytes = float(kv_read_bytes)
        self.param_read_bytes = float(param_read_bytes)
        self.bytes = self.kv_read_bytes + self.param_read_bytes
        self.chip = chip
        self.paged = bool(paged)
        self.block_size = block_size
        self.kv_dtype_bytes = kv_dtype_bytes
        self.tp = int(tp)
        self.comm_bytes = float(comm_bytes)
        t_compute = self.flops / chip.peak_flops
        t_memory = self.bytes / chip.hbm_bw
        t_ici = (self.comm_bytes / chip.ici_bw
                 if self.comm_bytes and chip.ici_bw else 0.0)
        self.time_s = max(t_compute, t_memory, t_ici)
        if t_ici >= t_compute and t_ici >= t_memory and t_ici > 0:
            self.bound = "ici"
        elif t_compute >= t_memory:
            self.bound = "compute"
        else:
            self.bound = "memory"
        self.tokens_per_s = (self.slots / self.time_s
                             if self.time_s > 0 else float("inf"))

    def to_dict(self):
        return {
            "schema_version": 1,
            "slots": self.slots, "cache_len": self.cache_len,
            "flops": self.flops,
            "kv_read_bytes": self.kv_read_bytes,
            "param_read_bytes": self.param_read_bytes,
            "bytes": self.bytes, "time_s": self.time_s,
            "bound": self.bound, "tokens_per_s": self.tokens_per_s,
            "paged": self.paged, "block_size": self.block_size,
            "kv_dtype_bytes": self.kv_dtype_bytes,
            "tp": self.tp, "comm_bytes": self.comm_bytes,
            "chip": self.chip.to_dict(),
        }


def decode_step_cost(*, num_layers, hidden_size, num_heads, vocab_size,
                     intermediate_size=None, slots=8, cache_len=512,
                     dtype_bytes=4, chip=None, paged=False,
                     mean_len=None, block_size=16, kv_dtype_bytes=None,
                     tp=1):
    """Static decode-step estimate (see `DecodeStepCost`).

    FLOPs per slot: the standard 2*N_params matmul work (QKV/out
    projections, FFN, tied LM head) + 4*cache_len*hidden attention
    work.  HBM bytes: every parameter once per STEP (amortized over
    slots) + each slot's K and V cache rows once.

    Dense (default) charges every slot ``cache_len`` rows — the
    provisioned worst case.  ``paged=True`` charges
    ``ceil(mean_len / block_size) * block_size`` rows per slot (the
    block-granular read the table-driven kernel actually streams;
    ``mean_len`` defaults to ``cache_len``), priced at
    ``kv_dtype_bytes`` per element (default ``dtype_bytes``; pass 1
    for int8 KV — the per-row per-head f32 scales are charged on
    top).  The paged-vs-dense ratio is the HBM argument ROADMAP item 1
    banks, and `tests/test_perf_gate.py` budgets it.

    ``tp > 1`` prices ONE CHIP of a `tp_serving.TPGenerationEngine`:
    layer weights, KV reads and attention/FFN flops divide by ``tp``
    (Megatron column/row shards + heads-sharded cache); the
    embedding/LM-head weights stay replicated (every chip computes
    full logits); and each layer adds two ring all-reduces over the
    ``[slots, hidden]`` activations, so per-step
    ``comm_bytes = 2 * L * ringfactor(tp) * slots * h * dtype`` —
    at tp=2 the ring factor ``2*(N-1)/N`` is exactly 1 and the
    closed form ``2*L*slots*h*dtype`` holds, the perf-gate pin."""
    from .comm import collective_wire_bytes

    if intermediate_size is None:
        intermediate_size = 4 * hidden_size
    tp = int(tp)
    if tp < 1:
        raise ValueError("tp must be >= 1, got %d" % tp)
    if tp > 1 and num_heads % tp:
        raise ValueError("tp=%d does not divide num_heads=%d"
                         % (tp, num_heads))
    h, L = float(hidden_size), int(num_layers)
    per_layer_params = (4 * h * h + 2 * h * intermediate_size) / tp
    params = L * per_layer_params + vocab_size * h
    if paged:
        if mean_len is None:
            mean_len = cache_len
        rows = -(-int(mean_len) // int(block_size)) * int(block_size)
        kvb = dtype_bytes if kv_dtype_bytes is None else kv_dtype_bytes
        kv_read = 2.0 * L * slots * rows * h * kvb / tp
        if kvb < dtype_bytes:
            # int8 rows carry f32 per-head scales the kernel also reads
            kv_read += 2.0 * L * slots * rows * num_heads * 4 / tp
    else:
        rows = cache_len
        kvb = dtype_bytes
        kv_read = 2.0 * L * slots * cache_len * h * dtype_bytes / tp
    attn_flops = 4.0 * rows * h / tp            # QK^T + PV per slot/layer
    flops = slots * (2.0 * params + L * attn_flops)
    param_read = params * dtype_bytes
    comm = (2.0 * L * collective_wire_bytes(
        "all-reduce", slots * h * dtype_bytes, tp) if tp > 1 else 0.0)
    return DecodeStepCost(slots, cache_len, flops, kv_read, param_read,
                          chip or ChipSpec.detect(), paged=paged,
                          block_size=int(block_size) if paged else None,
                          kv_dtype_bytes=kvb if paged else None,
                          tp=tp, comm_bytes=comm)
