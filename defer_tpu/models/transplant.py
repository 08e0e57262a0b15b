"""Weight transplant: external checkpoints -> GraphParams.

The reference ships weights as raw compressed arrays over sockets
(reference src/dispatcher.py:75-88, src/node.py:74-92) and relies on
Keras `set_weights` ordering (reference src/node.py:42). Here the
analogous machinery is a layout-aware importer: it walks the IR graph,
asks a `WeightSource` for each parameter, converts the source
framework's array layout to ours (NHWC activations / HWIO kernels — the
TPU-native layout), shape-checks, and returns a fresh GraphParams
pytree.

Two sources are built in:

  * `KerasWeights` — Keras-style `{layer_name: [arrays]}` in Keras's
    `get_weights()` ordering (conv kernels already HWIO, depthwise
    kernels (kh, kw, cin, mult)). `load_keras_h5` reads the dict out of
    a Keras `save_weights` HDF5 file.
  * `TorchStateDict` — a torch `state_dict` (conv kernels OIHW,
    linear (out, in), BN running stats), with a configurable node-name
    -> torch-prefix map.

`export_keras_weights` is the inverse (GraphParams -> Keras-layout
dict), giving a lossless round trip and an interop path back to the
reference's ecosystem.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from defer_tpu.graph.ir import Graph, GraphParams, OpNode
from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)


class TransplantError(ValueError):
    pass


# --------------------------------------------------------------------------
# Layout conversion, per op kind
# --------------------------------------------------------------------------

# Keras get_weights() ordering per op kind; None entries are skipped
# (parameters our init chose not to create, e.g. a disabled bias).
_KERAS_ORDER: dict[str, tuple[str, ...]] = {
    "conv": ("kernel", "bias"),
    "depthwise_conv": ("kernel", "bias"),
    "separable_conv": ("dw_kernel", "pw_kernel", "bias"),
    "dense": ("kernel", "bias"),
    "batch_norm": ("scale", "bias", "mean", "var"),
    # Keras Normalization stores [adapt_mean, adapt_variance, count];
    # count is bookkeeping with no analogue here and is never requested.
    "normalization": ("mean", "var", "count"),
}

_TORCH_KEYS: dict[str, dict[str, str]] = {
    "conv": {"kernel": "weight", "bias": "bias"},
    "depthwise_conv": {"kernel": "weight", "bias": "bias"},
    "dense": {"kernel": "weight", "bias": "bias"},
    "batch_norm": {
        "scale": "weight",
        "bias": "bias",
        "mean": "running_mean",
        "var": "running_var",
    },
    "layer_norm": {"scale": "weight", "bias": "bias"},
    "embedding": {"table": "weight"},
    "pos_embedding": {"table": "weight"},
}


def _from_keras(op: str, param: str, value: np.ndarray) -> np.ndarray:
    if op == "separable_conv" and param == "dw_kernel":
        kh, kw = value.shape[:2]
        return value.reshape(kh, kw, 1, -1)
    if op == "depthwise_conv" and param == "kernel":
        # (kh, kw, cin, mult) -> (kh, kw, 1, cin*mult). C-order flatten
        # puts output channel c*mult + m exactly where XLA's
        # feature_group_count=cin grouping expects it.
        kh, kw = value.shape[:2]
        return value.reshape(kh, kw, 1, -1)
    return value


def _to_keras(op: str, param: str, value: np.ndarray, attrs) -> np.ndarray:
    if op == "separable_conv" and param == "dw_kernel":
        kh, kw, _, cm = value.shape
        mult = int(attrs.get("depth_multiplier", 1))
        return value.reshape(kh, kw, cm // mult, mult)
    if op == "depthwise_conv" and param == "kernel":
        kh, kw, _, cm = value.shape
        mult = int(attrs.get("depth_multiplier", 1))
        return value.reshape(kh, kw, cm // mult, mult)
    return value


def tensor_to_numpy(value: Any) -> np.ndarray:
    """Coerce a checkpoint tensor (torch.Tensor — incl. bfloat16, which
    `.numpy()` rejects — or anything array-like) to a numpy array,
    without importing torch. The ONE coercion for every checkpoint-
    interop path (CNN transplant, llama, t5)."""
    if hasattr(value, "detach"):  # torch.Tensor
        value = value.detach().cpu()
        try:
            value = value.numpy()
        except TypeError:  # bfloat16: widen, then convert
            value = value.float().numpy()
    return np.asarray(value)


def _from_torch(op: str, param: str, value: np.ndarray) -> np.ndarray:
    if param == "kernel":
        if op == "conv":
            return np.transpose(value, (2, 3, 1, 0))  # OIHW -> HWIO
        if op == "depthwise_conv":
            # (cin*mult, 1, kh, kw) -> (kh, kw, 1, cin*mult); torch
            # groups=cin ordering matches XLA's (both c*mult + m).
            return np.transpose(value, (2, 3, 1, 0))
        if op == "dense":
            return np.transpose(value, (1, 0))  # (out, in) -> (in, out)
    return value


# --------------------------------------------------------------------------
# Weight sources
# --------------------------------------------------------------------------


class WeightSource:
    """Protocol: yield converted arrays for a node, or None to skip."""

    def get(self, node: OpNode, param: str, shape: tuple[int, ...]):
        raise NotImplementedError

    def keys_used(self) -> set[str]:
        raise NotImplementedError

    def all_keys(self) -> set[str]:
        raise NotImplementedError


@dataclasses.dataclass
class KerasWeights(WeightSource):
    """Keras-style `{layer_name: [arrays in get_weights() order]}`.

    `name_map` translates IR node names to source layer names (identity
    by default — the zoo's node naming is already Keras-shaped).

    `bn_missing` names the BN param a three-array BatchNormalization
    list is missing: Keras drops gamma from the FRONT for scale=False
    (the Inception family's config) and beta from the middle for
    center=False, so the array count alone cannot disambiguate.
    """

    weights: Mapping[str, Sequence[np.ndarray]]
    name_map: Callable[[str], str] = staticmethod(lambda n: n)
    bn_missing: str = "scale"

    def __post_init__(self) -> None:
        self._used: set[str] = set()
        if self.bn_missing not in ("scale", "bias"):
            raise TransplantError(
                f"bn_missing must be 'scale' or 'bias', got {self.bn_missing!r}"
            )

    def _present(self, op: str, n_arrays: int) -> tuple[str, ...]:
        order = _KERAS_ORDER[op]
        if op == "batch_norm" and n_arrays < 4:
            # Keras get_weights order is [gamma?][beta?] mean var, with
            # gamma/beta independently omitted by scale=False /
            # center=False — not truncated from the end.
            if n_arrays == 2:
                return ("mean", "var")
            if n_arrays == 3:
                keep = tuple(p for p in order if p != self.bn_missing)
                return keep
        # Other ops only ever omit the trailing bias (use_bias=False).
        return order[:n_arrays]

    def get(self, node: OpNode, param: str, shape):
        key = self.name_map(node.name)
        if key not in self.weights:
            return None
        order = _KERAS_ORDER.get(node.op)
        if order is None or param not in order:
            raise TransplantError(
                f"no Keras layout rule for op {node.op!r} param {param!r} "
                f"(node {node.name!r})"
            )
        arrays = list(self.weights[key])
        present = self._present(node.op, len(arrays))
        if param not in present:
            return None
        self._used.add(key)
        return _from_keras(node.op, param, np.asarray(arrays[present.index(param)]))

    def keys_used(self) -> set[str]:
        return self._used

    def all_keys(self) -> set[str]:
        return set(self.weights)


@dataclasses.dataclass
class TorchStateDict(WeightSource):
    """A torch ``state_dict`` source.

    `name_map` translates an IR node name to the torch module prefix
    (e.g. ``"conv1_conv" -> "conv1"``); the per-parameter suffix
    (``weight`` / ``bias`` / ``running_mean`` / ...) is appended by op
    kind. Identity prefix map by default.
    """

    state_dict: Mapping[str, Any]
    name_map: Callable[[str], str] = staticmethod(lambda n: n)

    def __post_init__(self) -> None:
        self._used: set[str] = set()

    def get(self, node: OpNode, param: str, shape):
        keys = _TORCH_KEYS.get(node.op)
        if keys is None or param not in keys:
            # Unknown op kinds are simply not covered by this source;
            # strict transplant() reports the node as missing, and
            # strict=False keeps its initialized values.
            return None
        key = f"{self.name_map(node.name)}.{keys[param]}"
        if key not in self.state_dict:
            return None
        value = tensor_to_numpy(self.state_dict[key])
        self._used.add(key)
        return _from_torch(node.op, param, value)

    def keys_used(self) -> set[str]:
        return self._used

    def all_keys(self) -> set[str]:
        # num_batches_tracked is BN bookkeeping with no analogue here;
        # exclude it so the unused-keys diagnostic stays signal.
        return {
            k for k in self.state_dict
            if not k.endswith(".num_batches_tracked")
        }


# --------------------------------------------------------------------------
# Transplant / export
# --------------------------------------------------------------------------


def transplant(
    graph: Graph,
    params: GraphParams,
    source: WeightSource,
    *,
    strict: bool = True,
    dtype: Any | None = None,
) -> dict:
    """Return a copy of `params` with every array the source provides.

    strict=True (default) raises if any parameterized node gets nothing
    from the source — the failure mode the reference hits silently when
    `set_weights` ordering drifts (reference src/node.py:42).
    """
    out: dict = {}
    missing: list[str] = []
    for node in graph.nodes:
        node_params = params.get(node.name, {})
        if not node_params:
            out[node.name] = node_params
            continue
        loaded = {}
        got_any = False
        for pname, cur in node_params.items():
            value = source.get(node, pname, tuple(cur.shape))
            if value is None:
                loaded[pname] = cur
                continue
            if tuple(value.shape) != tuple(cur.shape):
                raise TransplantError(
                    f"shape mismatch for {node.name}.{pname}: checkpoint "
                    f"{tuple(value.shape)} vs model {tuple(cur.shape)}"
                )
            loaded[pname] = jnp.asarray(value, dtype or cur.dtype)
            got_any = True
        if not got_any:
            missing.append(node.name)
        out[node.name] = loaded
    if strict and missing:
        raise TransplantError(
            f"source provided no weights for {len(missing)} parameterized "
            f"nodes, e.g. {missing[:5]}; pass strict=False to keep their "
            "initialized values"
        )
    unused = source.all_keys() - source.keys_used()
    if unused:
        # Typo'd layer names silently strand checkpoint arrays — the
        # reference's set_weights path has no such diagnostic at all
        # (reference src/node.py:42).
        log.warning(
            "transplant: %d checkpoint keys unused, e.g. %s",
            len(unused),
            sorted(unused)[:5],
        )
    return out


def export_keras_weights(
    graph: Graph, params: GraphParams
) -> dict[str, list[np.ndarray]]:
    """GraphParams -> Keras-layout `{layer: [arrays]}` (round-trippable
    through KerasWeights, and loadable into a same-architecture Keras
    model via `set_weights` for interop with the reference)."""
    out: dict[str, list[np.ndarray]] = {}
    node_map = graph.node_map
    for name, node_params in params.items():
        if not node_params:
            continue
        node = node_map[name]
        order = _KERAS_ORDER.get(node.op)
        if order is None:
            raise TransplantError(
                f"no Keras layout rule for op {node.op!r} (node {name!r})"
            )
        out[name] = [
            _to_keras(node.op, p, np.asarray(node_params[p]), node.attrs)
            for p in order
            if p in node_params
        ]
    return out


def _to_snake_case(name: str) -> str:
    """Keras's class-name -> object-name rule (Conv2D -> conv2d,
    BatchNormalization -> batch_normalization, ReLU -> re_lu)."""
    import re

    name = re.sub(r"\W+", "", name)
    name = re.sub("(.)([A-Z][a-z]+)", r"\1_\2", name)
    return re.sub("([a-z])([A-Z])", r"\1_\2", name).lower()


def _keras3_group_names(model_json) -> dict[str, str]:
    """h5 group name -> real layer name for a Keras 3 `.weights.h5`.

    Keras 3 names each layer's h5 group by snake-cased class name with
    a per-class counter in model.layers order (NOT by `layer.name`);
    the model JSON's config.layers order reproduces that assignment.
    """
    import json as _json

    spec = (
        _json.loads(model_json) if isinstance(model_json, str) else model_json
    )
    layers = spec.get("config", {}).get("layers", [])
    counters: dict[str, int] = {}
    mapping: dict[str, str] = {}
    for layer in layers:
        cls = layer.get("class_name", "")
        name = layer.get("name") or layer.get("config", {}).get("name")
        base = _to_snake_case(cls)
        idx = counters.get(base, 0)
        counters[base] = idx + 1
        mapping[base if idx == 0 else f"{base}_{idx}"] = name
    return mapping


def load_keras_h5(
    path: str, model_json=None
) -> dict[str, list[np.ndarray]]:
    """Read a Keras `save_weights` HDF5 file into `{layer: [arrays]}`.

    Supports both on-disk layouts: the classic topological layout
    (`layer_names` / `weight_names` attrs) that TF1/2-era Keras — the
    reference's environment — writes, and the Keras 3 `.weights.h5`
    layout (`layers/<object_name>/vars/<i>` datasets in
    `layer.weights` order, which matches `get_weights()` ordering).
    Keras 3 group names are per-class counters, not layer names; pass
    the model's `to_json()` string as `model_json` to resolve them to
    real layer names (otherwise the raw object names are returned).
    """
    import h5py

    out: dict[str, list[np.ndarray]] = {}
    with h5py.File(path, "r") as f:
        if "layers" in f and "layer_names" not in f.attrs:
            # Keras 3 layout.
            resolve = (
                _keras3_group_names(model_json) if model_json is not None
                else {}
            )
            layers_group = f["layers"]
            for lname in layers_group:
                g = layers_group[lname]
                if "vars" not in g:
                    continue
                vars_group = g["vars"]
                arrays = [
                    np.asarray(vars_group[k])
                    for k in sorted(vars_group, key=int)
                ]
                if arrays:
                    out[resolve.get(lname, lname)] = arrays
            return out
        root = f["model_weights"] if "model_weights" in f else f
        layer_names = [
            n.decode() if isinstance(n, bytes) else n
            for n in root.attrs.get("layer_names", list(root.keys()))
        ]
        for lname in layer_names:
            g = root[lname]
            weight_names = [
                n.decode() if isinstance(n, bytes) else n
                for n in g.attrs.get("weight_names", [])
            ]
            arrays = [np.asarray(g[w]) for w in weight_names]
            if arrays:
                out[lname] = arrays
    return out


# --------------------------------------------------------------------------
# Draft construction: shrink a GPT target into a speculation draft
# --------------------------------------------------------------------------

# Per-axis slice spec for each stacked-block parameter (after the
# leading layer axis): "d" = model width, "f" = FFN width, "kv" = the
# KV projection width (kv_heads * Dh — NEVER sliced: the draft must
# keep the target's kv_heads so its proposals come from the same
# attention geometry the verifier scores).
_DRAFT_STACK_AXES: dict[str, tuple[str, ...]] = {
    "wq": ("d", "d"),
    "wk": ("d", "kv"),
    "wv": ("d", "kv"),
    "wo": ("d", "d"),
    "w1": ("d", "f"),
    "w2": ("f", "d"),
    "w3": ("d", "f"),
    "ln1_scale": ("d",),
    "ln2_scale": ("d",),
    "ln1_bias": ("d",),
    "ln2_bias": ("d",),
    "bq": ("d",),
    "bk": ("kv",),
    "bv": ("kv",),
    "bo": ("d",),
    "b1": ("f",),
    "b2": ("d",),
}


def draft_width_geometry(cfg, width: float) -> tuple[int, int, int]:
    """(num_heads', dim', ffn_dim') for a width-pruned draft of `cfg`.

    Head count rounds to the nearest multiple of kv_heads (floor 1x)
    so GQA grouping survives the prune; Dh is untouched, so rope
    frequencies and per-head shapes stay target-identical and dim'
    follows the head count. FFN width scales freely (floor 1)."""
    if not (0.0 < width <= 1.0):
        raise TransplantError(
            f"width={width}: draft width fraction must be in (0, 1]"
        )
    kv = cfg.kv_heads
    dh = cfg.dh
    heads = kv * max(1, round(cfg.num_heads * width / kv))
    heads = min(heads, cfg.num_heads)
    ffn = max(1, round(cfg.ffn_dim * width))
    return heads, heads * dh, ffn


def make_draft(
    decoder,
    params: Mapping[str, Any],
    *,
    layers: int | None = None,
    width: float | None = None,
    dtype: Any = None,
):
    """Carve a small speculation draft out of a GPT target.

    Returns `(draft_decoder, draft_params)` where the draft is the
    target with the first `layers` blocks kept (layer truncation)
    and/or its query heads + FFN pruned to a `width` fraction
    (head/FFN slicing with the matching projection rows/columns
    re-stitched so the sliced tree is a valid transformer). Vocab,
    kv_heads, head dim, positions (learned table or rope base) and
    max_len are preserved — exactly the geometry `DraftLanes`
    validates against the target at server construction.

    `dtype="int8"` additionally routes the sliced tree through
    `models/quant.py::quantize_decoder_params` (weight-only symmetric
    int8 — the draft's HBM reads halve again); any other dtype casts
    float leaves (like `GptDecoder.cast_params`). The draft is an
    APPROXIMATION of the target — acceptance < 1 is the point; the
    verify forward keeps outputs token-identical regardless.
    """
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.quant import quantize_decoder_params

    cfg = getattr(decoder, "cfg", None)
    if cfg is None or "stack" not in params:
        raise TransplantError(
            "make_draft needs a GptDecoder-style (decoder, params) pair "
            "(a .cfg config and a params['stack'] block tree)"
        )
    from defer_tpu.parallel.transformer_stack import refuse_mechanisms

    refuse_mechanisms(cfg, "make_draft")
    if any(
        isinstance(v, dict) and "q" in v
        for v in list(params["stack"].values())
        + [params.get("token_embedding")]
        if v is not None
    ):
        raise TransplantError(
            "make_draft slices float params: quantized {'q','s'} leaves "
            "would lose their per-channel scales — build the draft from "
            "the float tree, then ask for dtype='int8'"
        )
    L = cfg.num_layers
    keep_l = L if layers is None else layers
    if not (1 <= keep_l <= L):
        raise TransplantError(
            f"layers={layers}: draft must keep between 1 and "
            f"{L} (the target's depth) blocks"
        )
    if width is None:
        heads, dim, ffn = cfg.num_heads, cfg.dim, cfg.ffn_dim
    else:
        heads, dim, ffn = draft_width_geometry(cfg, width)
    dims = {"d": dim, "f": ffn, "kv": cfg.kv_heads * cfg.dh}

    def cut(leaf, axes):
        idx = (slice(0, keep_l),) + tuple(
            slice(0, dims[a]) for a in axes
        )
        return jnp.asarray(leaf)[idx]

    stack = {}
    for k, v in params["stack"].items():
        if k not in _DRAFT_STACK_AXES:
            raise TransplantError(
                f"stack param {k!r} has no draft slice rule — drafts "
                "support plain GPT/llama decoder stacks (no MoE, no "
                "LoRA adapters; merge adapters first)"
            )
        stack[k] = cut(v, _DRAFT_STACK_AXES[k])
    out: dict[str, Any] = {"stack": stack}
    out["token_embedding"] = jnp.asarray(params["token_embedding"])[:, :dim]
    out["final_ln_scale"] = jnp.asarray(params["final_ln_scale"])[:dim]
    if "final_ln_bias" in params:
        out["final_ln_bias"] = jnp.asarray(params["final_ln_bias"])[:dim]
    if "pos_embedding" in params:
        out["pos_embedding"] = jnp.asarray(params["pos_embedding"])[:, :dim]
    if "lm_head" in params:
        out["lm_head"] = jnp.asarray(params["lm_head"])[:dim, :]

    dcfg = dataclasses.replace(
        cfg,
        num_layers=keep_l,
        num_heads=heads,
        num_kv_heads=cfg.kv_heads,
        dim=dim,
        ffn_dim=ffn,
    )
    draft = GptDecoder(dcfg, compute_dtype=decoder.compute_dtype)
    if dtype == "int8":
        out = quantize_decoder_params(out)
    elif dtype is not None:
        out = {
            k: jax.tree_util.tree_map(
                lambda a: a.astype(dtype)
                if jnp.issubdtype(a.dtype, jnp.floating)
                else a,
                v,
            )
            for k, v in out.items()
        }
    log.info(
        "draft: %d/%d layers, %d/%d heads, dim %d/%d, ffn %d/%d%s",
        keep_l, L, heads, cfg.num_heads, dim, cfg.dim, ffn, cfg.ffn_dim,
        " (int8)" if dtype == "int8" else "",
    )
    return draft, out
