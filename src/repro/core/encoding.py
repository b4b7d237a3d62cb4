"""Phase (i): semantic encoding via the semantic forest (paper section IV.1).

The semantic forest organises places into ``n_levels`` granularities, finest
(place name) to coarsest (place type).  A place name id is mapped to its code
at every level through composed parent lookups, producing the paper's
``E_type.E_class.E_name`` encoding as an int32 tensor ``[N, n_levels, L]``.

The forest is represented densely: ``parents[l]`` maps a level-(l+1) id to its
level-l parent id (level 0 = coarsest).  This is the array analogue of the
WordNet-derived ontology the paper describes, and generalises to any number of
levels (used by the Fig. 15 experiment, levels 2..6).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import EncodedBatch, TrajectoryBatch, PAD_PLACE

# Padding sentinels for encoded codes.  Using two *different* negative values
# for the two sides of a comparison guarantees padded positions never match
# (similarity.py relies on this).
PAD_CODE_A = -1
PAD_CODE_B = -2


@dataclasses.dataclass(frozen=True)
class SemanticForest:
    """A dense n-level semantic forest.

    parents[l][child_id] -> parent id at level l, for l in [0, n_levels-2];
    parents[l] maps level-(l+1) ids into level-l ids.
    sizes[l] = number of distinct codes at level l (coarsest first).
    """

    parents: tuple  # tuple of np.ndarray[int32]
    sizes: tuple    # tuple of int

    @property
    def num_levels(self) -> int:
        return len(self.sizes)

    @property
    def num_types(self) -> int:
        """Vocabulary size at the coarsest ("type") level — the SSH alphabet Q."""
        return self.sizes[0]

    @property
    def num_places(self) -> int:
        return self.sizes[-1]

    def level_maps(self) -> list[np.ndarray]:
        """For each level l, an array mapping place (name) id -> level-l code."""
        maps = [np.arange(self.sizes[-1], dtype=np.int32)]
        # walk from finest to coarsest, composing parent lookups
        for l in range(self.num_levels - 2, -1, -1):
            maps.append(self.parents[l][maps[-1]])
        maps.reverse()  # coarsest first
        return maps


def make_random_forest(
    num_types: int,
    classes_per_type: int,
    num_places: int,
    *,
    n_levels: int = 3,
    seed: int = 0,
) -> SemanticForest:
    """Generate a random semantic forest matching the paper's synthetic setup
    (30 types x 10 classes, 10,000 place names; 300 types for scalability).

    For ``n_levels != 3`` the intermediate levels are built by repeated
    uniform fan-out so Fig. 15's 2..6-level hierarchies are reproducible.
    """
    rng = np.random.default_rng(seed)
    if n_levels == 2:
        sizes = [num_types, num_places]
    elif n_levels == 3:
        sizes = [num_types, num_types * classes_per_type, num_places]
    else:
        # geometric interpolation of level sizes between types and places
        ratio = (num_places / num_types) ** (1.0 / (n_levels - 1))
        sizes = [max(1, int(round(num_types * ratio**i))) for i in range(n_levels)]
        sizes[0], sizes[-1] = num_types, num_places
        for i in range(1, n_levels):  # enforce monotone growth
            sizes[i] = max(sizes[i], sizes[i - 1])
    parents = []
    for l in range(len(sizes) - 1):
        # each level-(l+1) id gets a uniformly random level-l parent, but we
        # guarantee every parent has at least one child by round-robin seeding
        child_n, parent_n = sizes[l + 1], sizes[l]
        p = rng.integers(0, parent_n, size=child_n).astype(np.int32)
        p[:parent_n] = np.arange(parent_n, dtype=np.int32)
        rng.shuffle(p)
        parents.append(p)
    return SemanticForest(parents=tuple(parents), sizes=tuple(sizes))


def forest_tables(forest: SemanticForest) -> jnp.ndarray:
    """Stack the level maps into one int32 [n_levels, num_places] table."""
    return jnp.asarray(np.stack(forest.level_maps(), axis=0))


def encode_codes(
    places: jnp.ndarray,
    tables: jnp.ndarray,
    *,
    pad_code: int = PAD_CODE_A,
) -> jnp.ndarray:
    """Raw-array encoding: place ids [N, L] -> codes [N, n_levels, L].

    jit-friendly (a single gather per level, one fused gather in XLA) and
    jax-traceable on raw arrays, so the sharded pipeline can run it *inside*
    the shard_map program on each shard's local rows — the full code table
    then never materializes replicated on the host.
    """
    # gather over the transposed ids ([L, N]: N on the lanes) and transpose
    # the codes back; gathering straight into [N, n_levels, L] rows of
    # L = 10 lanes takes the TPU compiler over a minute at N = 250,000
    ids = places.T
    safe = jnp.where(ids == PAD_PLACE, 0, ids)
    codes = jnp.take(tables, safe.reshape(-1), axis=1, mode="clip")
    codes = codes.reshape((tables.shape[0],) + ids.shape).astype(jnp.int32)
    codes = jnp.where(ids == PAD_PLACE, pad_code, codes)  # [n_levels, L, N]
    return jnp.transpose(codes, (2, 0, 1))


def encode_types(
    places: jnp.ndarray,
    tables: jnp.ndarray,
    *,
    pad_code: int = PAD_CODE_A,
) -> jnp.ndarray:
    """Coarsest-level ("type") codes only: place ids [N, L] -> int32 [N, L].

    The driver-side view the sharded engine uses for capacity planning: join
    keys derive from level 0, so planning needs one [N, L] gather — not the
    [N, n_levels, L] code table, which stays device-resident.
    """
    safe = jnp.where(places == PAD_PLACE, 0, places)
    types = tables[0, safe].astype(jnp.int32)
    return jnp.where(places == PAD_PLACE, pad_code, types)


def encode_batch(
    batch: TrajectoryBatch,
    tables: jnp.ndarray,
    *,
    pad_code: int = PAD_CODE_A,
) -> EncodedBatch:
    """Map each place id through every forest level: [N, L] -> [N, n_levels, L].

    Padded positions become ``pad_code``.
    """
    codes = encode_codes(batch.places, tables, pad_code=pad_code)
    return EncodedBatch(codes=codes, lengths=batch.lengths)


def type_codes(encoded: EncodedBatch) -> jnp.ndarray:
    """The coarsest-level view used by SSH: int32 [N, L]."""
    return encoded.codes[:, 0, :]


def encode_places(place_ids: Sequence[int], tables: np.ndarray) -> list[str]:
    """Human-readable dotted encodings ("E_type.E_class.E_name") for demos."""
    out = []
    tables = np.asarray(tables)
    for p in place_ids:
        out.append(".".join(str(int(tables[l, p])) for l in range(tables.shape[0])))
    return out
