"""Wide & Deep model: functional forward + .wdl spec.

Parity target: core/dtrain/wdl/WideAndDeep.java:50 (forward :163) — dense
input layer + per-categorical-field embeddings feeding an MLP (deep), plus a
wide tower of per-field vocab weights and a linear dense part; combined
logits through sigmoid. The reference walks layer objects per record; here
the whole batch is embeddings-gather + matmuls in one jit program, with the
embedding tables shardable over a `model` mesh axis (tensor parallelism for
10k+-vocab fields — SURVEY §2.8 TP obligation).

Inputs: dense [n, Dn] float32 (z-scaled numerics) and codes [n, Dc] int32
(categorical bin indices incl. missing slot, from the CleanedData matrix).
"""

from __future__ import annotations

import io
import json
import os
import struct
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

MAGIC = b"STWD"
FORMAT_VERSION = 1


@dataclass
class WDLParams:
    """All arrays, grouped. Flattens to one vector for the update rules."""

    embed: List[np.ndarray]  # per cat field [vocab_f, E]
    wide: List[np.ndarray]  # per cat field [vocab_f]
    wide_dense: np.ndarray  # [Dn]
    dense_layers: List[Dict[str, np.ndarray]]  # deep MLP on [Dn + Dc*E]
    bias: np.ndarray  # [1]


def init_wdl_params(
    n_dense: int,
    vocab_sizes: List[int],
    embed_dim: int,
    hidden: List[int],
    seed: int = 0,
) -> WDLParams:
    rng = np.random.default_rng(seed)
    embed = [
        rng.normal(0, 0.05, size=(v, embed_dim)).astype(np.float32)
        for v in vocab_sizes
    ]
    wide = [np.zeros(v, dtype=np.float32) for v in vocab_sizes]
    deep_in = n_dense + len(vocab_sizes) * embed_dim
    sizes = [deep_in] + list(hidden) + [1]
    dense_layers = []
    for fi, fo in zip(sizes[:-1], sizes[1:]):
        limit = np.sqrt(6.0 / (fi + fo))
        dense_layers.append({
            "W": rng.uniform(-limit, limit, size=(fi, fo)).astype(np.float32),
            "b": np.zeros(fo, dtype=np.float32),
        })
    return WDLParams(
        embed=embed,
        wide=wide,
        wide_dense=np.zeros(n_dense, dtype=np.float32),
        dense_layers=dense_layers,
        bias=np.zeros(1, dtype=np.float32),
    )


def wdl_arrays(p: WDLParams) -> List[np.ndarray]:
    out = list(p.embed) + list(p.wide) + [p.wide_dense]
    for layer in p.dense_layers:
        out.extend([layer["W"], layer["b"]])
    out.append(p.bias)
    return out


def wdl_shapes(p: WDLParams) -> List[Tuple[int, ...]]:
    return [tuple(a.shape) for a in wdl_arrays(p)]


def flatten_wdl(p: WDLParams) -> np.ndarray:
    return np.concatenate([np.asarray(a).ravel() for a in wdl_arrays(p)])


def unflatten_wdl_from_shapes(flat, shapes, n_cat: int) -> WDLParams:
    """flat (np or jnp) -> WDLParams-like structure of same array type.
    Shape-only signature so jit closures need not retain parameter arrays."""
    parts, off = [], 0
    for shp in shapes:
        size = int(np.prod(shp))
        parts.append(flat[off : off + size].reshape(shp))
        off += size
    embed = parts[:n_cat]
    wide = parts[n_cat : 2 * n_cat]
    wide_dense = parts[2 * n_cat]
    rest = parts[2 * n_cat + 1 : -1]
    dense_layers = [
        {"W": rest[i], "b": rest[i + 1]} for i in range(0, len(rest), 2)
    ]
    return WDLParams(embed=embed, wide=wide, wide_dense=wide_dense,
                     dense_layers=dense_layers, bias=parts[-1])


def unflatten_wdl(flat, template: WDLParams) -> WDLParams:
    return unflatten_wdl_from_shapes(
        flat, wdl_shapes(template), len(template.embed)
    )


def wdl_plane(embed, wide, first_w, dense, codes, onehot=None):
    """One lookup a categorical field: the field's embedding row and its wide
    weight sit side by side in a `[vocab_f, E + 1]` table put together here
    from `embed[f]` and `wide[f]` (a pass over the table's rows, not the
    data's), and one gather reads both. The gathered planes are never cut
    apart (a column cut from a `[n, E + 1]` plane is a pass over the rows of
    its own, as dear as the lookup it saves): they are concatenated whole.

    Under `jax.grad` a lookup has one of two transposes, and the assembly's
    transpose splits what it gives into the gradients of `embed[f]` and
    `wide[f]`:
    - the gather's own, one scatter-add a field into its table, which costs
      a pass over the rows whatever the table's size (PERF.md, section 5).
      Every table takes it without `onehot`, and with it every table that
      is not among its `fields` (those of more than 4,096 rows always);
    - with `onehot` = (fields, codes_t, interpret), the tables of `fields`
      take ONE call of the one-hot kernel for all of them
      (`ops/onehot_grad_pallas.py`: a contraction on the MXU whose cost
      grows with the table's rows, where the scatter's does not). The
      trainer gives it the tables of at most `ROWS_CAP` = 4,096 rows that
      one call holds in VMEM (`small_fields`): a cap that keeps a table's
      part of the call to 32 tiles and lies under the kernel's measured
      break-even with the scatter, near 23,000 rows (PERF.md, section 7).
      `codes_t` is `codes_operand` of those fields, made once a program;
      their forward is the same gather.

    Returns the plane `h` [n, Dn + Dc x (E + 1)]; the tower's first weight
    `first_w` [Dn + Dc x E, H] given a zero row at every wide column, so
    `h @ w` is the first layer on the dense columns and the embeddings alone;
    and the 0/1 selector of the wide columns: `jnp.dot(h, is_wide)` at
    `HIGHEST` is the fields' wide sum, the wide weight f32 from table to
    logit and back."""
    import jax.numpy as jnp

    first_w = jnp.asarray(first_w)
    tables, idxs = [], []
    for f, (emb, wd) in enumerate(zip(embed, wide, strict=True)):
        # params may be host numpy (loaded spec)
        tb = jnp.concatenate([jnp.asarray(emb), jnp.asarray(wd)[:, None]],
                             axis=1)
        tables.append(tb)
        idxs.append(jnp.clip(codes[:, f], 0, tb.shape[0] - 1))
    looked = {}
    if onehot is not None:
        from shifu_tpu.ops import onehot_grad_pallas

        fields, codes_t, interpret = onehot
        looked = dict(zip(fields, onehot_grad_pallas.lookups(
            [tables[f] for f in fields], [idxs[f] for f in fields], codes_t,
            interpret)))
    pieces, at = [dense], dense.shape[1]
    w_rows = [first_w[:at]]
    no_row = jnp.zeros((1, first_w.shape[1]), first_w.dtype)
    is_wide = [0.0] * at
    for f, (emb, tb, idx) in enumerate(zip(embed, tables, idxs)):
        pieces.append(looked[f] if f in looked else tb[idx])
        e = emb.shape[1]
        w_rows += [first_w[at:at + e], no_row]
        is_wide += [0.0] * e + [1.0]
        at += e
    h = jnp.concatenate(pieces, axis=1)
    return h, jnp.concatenate(w_rows, axis=0), jnp.asarray(is_wide, h.dtype)


def wdl_forward(p: WDLParams, dense, codes, activations: List[str],
                logits_only: bool = False, onehot=None):
    """dense [n, Dn], codes [n, Dc] -> [n] probability (or raw logit).

    One lookup a categorical field for its embedding row and its wide weight
    together (`wdl_plane`); the tower reads the whole plane through its first
    weight with zero rows at the wide columns, and the wide sum is a dot with
    the 0/1 selector of those columns at `HIGHEST`.

    The three named scopes are op metadata only (the `tf_op` a profiler
    trace shows): `wdl.embed` the per-field lookups and the plane, `wdl.deep`
    the tower, `wdl.wide` the selector dot and the dense dot. Under
    `jax.grad` their halves read `jvp(wdl.embed)`, the gathers, and
    `transpose(jvp(wdl.embed))`, one scatter-add a field into its
    `[vocab_f, E + 1]` table, or for `onehot`'s small tables the one-hot
    kernel (`wdl_plane`)."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.nn import activation_fn

    with jax.named_scope("wdl.embed"):
        h, first_w, is_wide = wdl_plane(
            p.embed, p.wide, p.dense_layers[0]["W"], dense, codes, onehot)
    with jax.named_scope("wdl.deep"):
        weights = [first_w] + [layer["W"] for layer in p.dense_layers[1:]]
        x = h
        n_hidden = len(p.dense_layers) - 1
        for i in range(n_hidden):
            act = activation_fn(activations[i % len(activations)] if activations else "relu")
            x = act(x @ weights[i] + p.dense_layers[i]["b"])
        deep_logit = (x @ weights[-1] + p.dense_layers[-1]["b"])[:, 0]

    with jax.named_scope("wdl.wide"):
        wide_logit = dense @ jnp.asarray(p.wide_dense) + jnp.dot(
            h, is_wide, precision=jax.lax.Precision.HIGHEST)

    logit = deep_logit + wide_logit + jnp.asarray(p.bias)[0]
    if logits_only:
        return logit
    return 1.0 / (1.0 + jnp.exp(-logit))


@dataclass
class WDLModelSpec:
    hidden: List[int]
    activations: List[str]
    embed_dim: int
    dense_columns: List[str]
    cat_columns: List[str]
    vocab_sizes: List[int]
    # raw-record scoring info
    norm_specs: List[Dict[str, Any]] = field(default_factory=list)  # dense cols
    norm_cutoff: float = 4.0
    categories: List[List[str]] = field(default_factory=list)  # per cat col
    norm_type: str = "ZSCALE"
    algorithm: str = "WDL"
    params: Optional[WDLParams] = None
    train_error: Optional[float] = None
    valid_error: Optional[float] = None

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        arrays = wdl_arrays(self.params)
        head = {
            "formatVersion": FORMAT_VERSION,
            "algorithm": "WDL",
            "hidden": self.hidden,
            "activations": self.activations,
            "embedDim": self.embed_dim,
            "denseColumns": self.dense_columns,
            "catColumns": self.cat_columns,
            "vocabSizes": self.vocab_sizes,
            "normSpecs": self.norm_specs,
            "normCutoff": self.norm_cutoff,
            "categories": self.categories,
            "normType": self.norm_type,
            "trainError": self.train_error,
            "validError": self.valid_error,
            "shapes": [list(s) for s in wdl_shapes(self.params)],
        }
        head_bytes = json.dumps(head).encode("utf-8")
        buf = io.BytesIO()
        buf.write(MAGIC)
        buf.write(struct.pack("<I", len(head_bytes)))
        buf.write(head_bytes)
        buf.write(flatten_wdl(self.params).astype("<f4").tobytes())
        with open(path, "wb") as fh:
            fh.write(buf.getvalue())

    @classmethod
    def load(cls, path: str) -> "WDLModelSpec":
        with open(path, "rb") as fh:
            data = fh.read()
        if data[:4] != MAGIC:
            raise ValueError(f"{path}: not a shifu-tpu .wdl model")
        (hlen,) = struct.unpack("<I", data[4:8])
        head = json.loads(data[8 : 8 + hlen].decode("utf-8"))
        flat = np.frombuffer(data[8 + hlen :], dtype="<f4").copy()
        spec = cls(
            hidden=head["hidden"],
            activations=head["activations"],
            embed_dim=head["embedDim"],
            dense_columns=head["denseColumns"],
            cat_columns=head["catColumns"],
            vocab_sizes=head["vocabSizes"],
            norm_specs=head.get("normSpecs", []),
            norm_cutoff=float(head.get("normCutoff", 4.0)),
            categories=head.get("categories", []),
            norm_type=head.get("normType", "ZSCALE"),
            train_error=head.get("trainError"),
            valid_error=head.get("validError"),
        )
        template = init_wdl_params(
            len(spec.dense_columns), spec.vocab_sizes, spec.embed_dim,
            spec.hidden,
        )
        spec.params = unflatten_wdl(flat, template)
        return spec

    def independent(self) -> "IndependentWDLModel":
        return IndependentWDLModel(self)


class IndependentWDLModel:
    """Zero-dependency scorer (parity: wdl/IndependentWDLModel.java:46)."""

    def __init__(self, spec: WDLModelSpec):
        self.spec = spec
        self._fwd = None

    @classmethod
    def load(cls, path: str) -> "IndependentWDLModel":
        return cls(WDLModelSpec.load(path))

    def inputs_from_raw(self, data) -> Tuple[np.ndarray, np.ndarray]:
        """ColumnarData -> (dense [n, Dn], codes [n, Dc]) using the embedded
        norm plan (dense) and category lists."""
        from shifu_tpu.norm.normalizer import apply_norm_plan, plan_from_json
        from shifu_tpu.stats.binning import categorical_bin_index

        plan = plan_from_json({
            "normType": self.spec.norm_type,
            "cutoff": self.spec.norm_cutoff,
            "columns": self.spec.norm_specs,
        })
        dense = (
            apply_norm_plan(plan, data)
            if plan.specs
            else np.zeros((data.n_rows, 0), np.float32)
        )
        codes = np.zeros((data.n_rows, len(self.spec.cat_columns)), np.int32)
        for f, name in enumerate(self.spec.cat_columns):
            cats = self.spec.categories[f]
            miss = data.missing_mask(name)
            codes[:, f] = categorical_bin_index(data.column(name), cats, miss)
        return dense, codes

    def compute_parts(self, dense: np.ndarray, codes: np.ndarray) -> np.ndarray:
        import jax

        if self._fwd is None:
            spec = self.spec

            self._fwd = jax.jit(
                lambda d, c: wdl_forward(spec.params, d, c, spec.activations)
            )
        return np.asarray(
            self._fwd(np.asarray(dense, np.float32), np.asarray(codes, np.int32))
        )

    def compute_raw(self, data) -> np.ndarray:
        dense, codes = self.inputs_from_raw(data)
        return self.compute_parts(dense, codes)

    def compute(self, x) -> np.ndarray:  # ModelRunner protocol fallback
        raise NotImplementedError(
            "WDL scoring needs (dense, codes); use compute_parts/compute_raw"
        )
