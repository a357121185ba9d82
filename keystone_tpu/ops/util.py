"""Utility nodes (reference src/main/scala/nodes/util/)."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import trace
from ..core.pipeline import FunctionNode, Transformer, node


@node(data_fields=(), meta_fields=("num_classes",))
class ClassLabelIndicatorsFromIntLabels(Transformer):
    """Int label -> ±1 one-hot indicator vector
    (reference nodes/util/ClassLabelIndicators.scala:11-21): -1 everywhere,
    +1 at the class index."""

    def __init__(self, num_classes: int):
        if num_classes < 2:
            raise ValueError("Must have at least two classes")
        self.num_classes = num_classes

    def __call__(self, labels):
        with trace.host("dispatch", "label_indicators"):  # four eager programs
            labels = jnp.asarray(labels)
            eye = jnp.eye(self.num_classes, dtype=jnp.float32)
            return 2.0 * eye[labels] - 1.0


@node(data_fields=(), meta_fields=("num_classes",))
class ClassLabelIndicatorsFromIntArrayLabels(Transformer):
    """Multi-label variant (reference ClassLabelIndicators.scala:24-38):
    takes a ±1 multi-hot from a padded [N, max_labels] int array (pad = -1)."""

    def __init__(self, num_classes: int):
        if num_classes < 2:
            raise ValueError("Must have at least two classes")
        self.num_classes = num_classes

    def __call__(self, label_arrays):
        out = []
        for labels in label_arrays:
            v = np.full(self.num_classes, -1.0, dtype=np.float32)
            for l in np.asarray(labels).ravel():
                if l >= 0:
                    v[int(l)] = 1.0
            out.append(v)
        return jnp.asarray(np.stack(out))


@node(data_fields=(), meta_fields=())
class MaxClassifier(Transformer):
    """argmax over the score vector (reference nodes/util/MaxClassifier.scala:9-11)."""

    def __call__(self, batch):
        return jnp.argmax(batch, axis=-1)


@node(data_fields=(), meta_fields=("k",))
class TopKClassifier(Transformer):
    """Top-k class indices, best first (reference nodes/util/TopKClassifier.scala:9-12)."""

    def __init__(self, k: int):
        self.k = k

    def __call__(self, batch):
        _, idx = jax.lax.top_k(batch, self.k)
        return idx


@node(data_fields=(), meta_fields=("dtype",))
class Cast(Transformer):
    """dtype cast; the reference's FloatToDouble
    (nodes/util/FloatToDouble.scala:9-11) generalized."""

    def __init__(self, dtype):
        self.dtype = dtype

    def __call__(self, batch):
        return batch.astype(self.dtype)


FloatToDouble = Cast  # alias; pass jnp.float64 (requires x64) or keep f32


@node(data_fields=(), meta_fields=())
class MatrixVectorizer(Transformer):
    """Flatten each per-example matrix to a vector
    (reference nodes/util/MatrixVectorizer.scala:9-11).  Column-major order to
    match Breeze's DenseMatrix.toDenseVector layout."""

    def __call__(self, batch):
        n = batch.shape[0]
        return jnp.swapaxes(batch, -1, -2).reshape(n, -1)


class ZipVectors(FunctionNode):
    """Concatenate a sequence of feature batches along the feature axis
    (reference nodes/util/ZipVectors.scala:10-15).  Co-sharded arrays concat
    with zero communication."""

    def __call__(self, batches: Sequence):
        return jnp.concatenate(list(batches), axis=-1)

    @staticmethod
    def apply(batches):
        return jnp.concatenate(list(batches), axis=-1)


@node(data_fields=("groups",), meta_fields=())
class GroupConcatFeaturizer(Transformer):
    """The MnistRandomFFT featurize phase as ONE chainable (and
    checkpointable) node: each GROUP of per-FFT chains runs on the same
    input batch, ZipVectors concatenates within the group, and the groups
    concatenate along the feature axis — ``[n, d] -> [n, G * group_width]``.

    This exists for the serving path (ISSUE 8): the fit loop keeps feeding
    :class:`~..solvers.block.BlockLinearMapper` the per-group batches
    directly (streaming evaluation wants blocks), but a *fitted* pipeline
    shipped to an endpoint must be one Transformer chain —
    ``GroupConcatFeaturizer >> model >> MaxClassifier`` — whose concatenated
    output the model's ``VectorSplitter`` cuts back into exactly the
    per-group blocks (each group is ``block_size`` wide by construction),
    so served scores are bit-equal to the fit-path apply.  ``groups`` is a
    data field: the chains are registered-node Pipelines, so the whole
    thing checkpoints through ``core.checkpoint`` and flows through jit as
    a pytree (fitted arrays stay program arguments, not baked constants).
    """

    def __init__(self, groups: Sequence[Sequence[Transformer]]):
        self.groups = tuple(tuple(g) for g in groups)

    def __call__(self, batch):
        return jnp.concatenate(
            [
                ZipVectors.apply([chain(batch) for chain in group])
                for group in self.groups
            ],
            axis=-1,
        )

    def __repr__(self):
        return (
            f"GroupConcatFeaturizer({len(self.groups)} groups x "
            f"{len(self.groups[0]) if self.groups else 0} chains)"
        )


class VectorSplitter(FunctionNode):
    """Split [N, d] features into ⌈d/block_size⌉ feature blocks — the
    model-parallel decomposition primitive
    (reference nodes/util/VectorSplitter.scala:10-36).  The last block may be
    short, matching the reference's slice semantics."""

    def __init__(self, block_size: int, num_features: int | None = None):
        self.block_size = block_size
        self.num_features = num_features

    def __call__(self, data):
        d = self.num_features or data.shape[-1]
        return [
            data[..., i : min(i + self.block_size, d)]
            for i in range(0, d, self.block_size)
        ]

    def split_vector(self, vec):
        return self(vec)

    def num_blocks(self, d: int | None = None) -> int:
        d = d or self.num_features
        return -(-d // self.block_size)
