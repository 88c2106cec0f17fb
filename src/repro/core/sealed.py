"""SealedTensor — ciphertext-at-rest representation of a tensor in untrusted HBM.

Implements the paper's Rules 1 & 2: a tensor that leaves the trust boundary
(on-die VMEM / the host enclave) exists only as counter-mode ciphertext plus a
sidecar of per-chunk MAC tags.

Design for distribution (the departure from the paper's flat DRAM buffers):
the ciphertext KEEPS THE TENSOR'S SHAPE, as the matching-width unsigned int
dtype (bf16 -> uint16 noise, f32 -> uint32 noise).  Counter-mode is a bitwise
XOR, so this is exact — and it means a SealedTensor shards under pjit with the
*same PartitionSpec* as its plaintext, and MAC tags (chunked along the last
axis) are shard-local.  Metadata (tags + nonce) is a separate small buffer,
matching the paper's "newly-allocated buffer in the off-chip DRAM".

SealedTensor is a registered pytree, so sealed values flow through jit /
shard_map / checkpointing like any other array.  The nonce is traced data,
because re-sealing inside a step bumps it (freshness).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import cipher, mac
from .policy import Protection, SealedSpec


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SealedTensor:
    ct: jax.Array            # uintN[shape] ciphertext (plaintext bits if NONE)
    tags: jax.Array          # uint32[..., n_chunks] block tags (empty if CTR/NONE)
    nonce: jax.Array         # uint32 scalar — counter uniqueness + freshness
    dtype: Any               # static: plaintext dtype
    spec: SealedSpec         # static

    def tree_flatten(self):
        return (self.ct, self.tags, self.nonce), (self.dtype, self.spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ct, tags, nonce = children
        dtype, spec = aux
        return cls(ct, tags, nonce, dtype, spec)

    @property
    def shape(self):
        return self.ct.shape

    @property
    def nbytes_ct(self) -> int:
        return int(np.prod(self.ct.shape)) * jnp.dtype(self.ct.dtype).itemsize

    @property
    def nbytes_meta(self) -> int:
        return int(np.prod(self.tags.shape)) * 4 + 4


def _mac_key(key: jax.Array, nonce: jax.Array, spec: SealedSpec) -> jax.Array:
    """Nonce-bound MAC key => replaying an old (ct, tags) pair fails (freshness)."""
    y0, y1 = cipher.threefry2x32(key, jnp.asarray(nonce, jnp.uint32),
                                 jnp.asarray(spec.mac_domain, jnp.uint32))
    return jnp.stack([y0, y1])


def seal(x: jax.Array, key: jax.Array, nonce, spec: SealedSpec) -> SealedTensor:
    """Seal a tensor: CTR-encrypt + per-chunk MAC over the *ciphertext*.

    Encrypt-then-MAC: tags authenticate what actually sits in untrusted memory.
    """
    nonce = jnp.asarray(nonce, jnp.uint32)
    x = jnp.asarray(x)
    if spec.protection is Protection.NONE:
        ct = jax.lax.bitcast_convert_type(x, cipher.uint_dtype_for(x.dtype))
        return SealedTensor(ct, jnp.zeros((0,), jnp.uint32), nonce, x.dtype, spec)
    ct = cipher.seal_bits(x, key, nonce)
    if spec.protection.authenticates:
        tags = mac.block_tags(ct, _mac_key(key, nonce, spec), spec.chunk_words,
                              spec.mac_domain)
    else:
        tags = jnp.zeros((0,), jnp.uint32)
    return SealedTensor(ct, tags, nonce, x.dtype, spec)


def unseal(st: SealedTensor, key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Unseal: verify chunk tags, decrypt.  Returns (tensor, ok_predicate).

    ``ok`` is a traced bool — inside a step the caller gates outputs on it
    (tamper => poisoned result) rather than branching, mirroring how the
    accelerator's security interface rejects unauthenticated fetches.
    """
    if st.spec.protection is Protection.NONE:
        return jax.lax.bitcast_convert_type(st.ct, st.dtype), jnp.bool_(True)
    if st.spec.protection.authenticates:
        ok = jnp.all(mac.verify_block_tags(st.ct, _mac_key(key, st.nonce, st.spec),
                                           st.spec.chunk_words, st.tags,
                                           st.spec.mac_domain))
    else:
        ok = jnp.bool_(True)
    x = cipher.unseal_bits(st.ct, key, st.nonce, st.dtype)
    return x, ok


def reseal(st: SealedTensor, x: jax.Array, key: jax.Array) -> SealedTensor:
    """Write a new value into a sealed slot: bump nonce, re-encrypt, re-MAC."""
    return seal(x, key, st.nonce + jnp.uint32(1), st.spec)


# ---------------------------------------------------------------------------
# nonce-lane budget: seal_tree spaces leaf nonces TREE_LEAF_STRIDE apart and
# reseal bumps +1 per step, so leaf i's lane walks toward leaf i+1's base.
# More than TREE_LEAF_STRIDE - 1 resealings under one key would *reuse
# keystream across leaves* (counter-mode two-time pad).  The nonce is traced
# data inside jitted steps, so the budget is enforced host-side: one
# ResealCounter per sealed tree, bumped once per reseal_tree application.
# ---------------------------------------------------------------------------

TREE_LEAF_STRIDE = 131
MAX_TREE_RESEALS = TREE_LEAF_STRIDE - 1


class NonceLaneExhausted(RuntimeError):
    """The next reseal would walk a leaf's nonce into the next leaf's lane."""


@dataclasses.dataclass
class ResealCounter:
    """Host-side guard for a sealed tree's per-leaf nonce lanes.

    ``note()`` before (or as) each reseal; once the budget is spent the guard
    raises instead of letting lanes touch — the owner must then re-seal under
    a fresh epoch (e.g. ``SecureChannel.refresh_tree``) and ``reset()``.
    """
    limit: int = MAX_TREE_RESEALS
    count: int = 0

    @property
    def remaining(self) -> int:
        return self.limit - self.count

    @property
    def exhausted(self) -> bool:
        return self.count >= self.limit

    def headroom(self) -> dict:
        """Monitor-facing budget report (obs/monitor.py headroom source)."""
        return {"source": "reseal_lanes", "limit": self.limit,
                "count": self.count, "remaining": self.remaining}

    def note(self, n: int = 1) -> None:
        if self.count + n > self.limit:
            raise NonceLaneExhausted(
                f"reseal #{self.count + n} would cross the {self.limit}-"
                "reseal nonce-lane budget (keystream reuse across leaves) — "
                "bump the epoch / re-seal the tree under fresh nonces first")
        self.count += n

    def reset(self) -> None:
        self.count = 0


@dataclasses.dataclass
class NonceSpanGuard:
    """Host-side budget for a *reserved* nonce span (e.g. one KV page's lane).

    A caller that reserved ``span`` consecutive counter values via
    ``SecureChannel.fresh_nonce(span=...)`` may bump the base nonce at most
    ``span - 1`` times before it would walk into the next reservation —
    counter-mode keystream reuse across two sealed objects.  ``spend()``
    before (or as) each bump; exhaustion raises instead of letting lanes
    touch.  Used by the paged KV pool for page close / reopen bumps.
    """
    span: int
    spent: int = 0

    @property
    def remaining(self) -> int:
        return max(0, self.span - 1 - self.spent)

    def headroom(self) -> dict:
        """Monitor-facing budget report (obs/monitor.py headroom source)."""
        return {"source": "page_nonce", "span": self.span,
                "spent": self.spent, "remaining": self.remaining}

    def spend(self, n: int = 1) -> None:
        if self.spent + n > self.span - 1:
            raise NonceLaneExhausted(
                f"nonce bump #{self.spent + n} would cross the reserved "
                f"span of {self.span} (keystream reuse with the next "
                "reservation) — reseal under a fresh nonce lane first")
        self.spent += n


# ---------------------------------------------------------------------------
# pytree-level helpers: seal/unseal whole parameter trees
# ---------------------------------------------------------------------------

def is_sealed(x) -> bool:
    return isinstance(x, SealedTensor)


_seal_leaf = {donate: jax.jit(seal, static_argnames=("spec",),
                              donate_argnums=(0,) if donate else ())
              for donate in (False, True)}


def seal_tree(tree, key: jax.Array, spec: SealedSpec, nonce_base: int = 0,
              *, donate: bool = False):
    """Seal every array leaf of a pytree, with distinct per-leaf nonces
    spaced TREE_LEAF_STRIDE apart (the ResealCounter budget above).

    Each leaf seals in one compiled program, so the keystream and MAC work
    stream through the ciphertext instead of materializing leaf-sized
    temporaries op by op.  ``donate`` hands each plaintext leaf to its
    program, which writes the ciphertext into the leaf's buffer: the device
    never holds both copies of the tree, and the caller's leaves are
    deleted.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    seal_leaf = _seal_leaf[donate]
    sealed = [seal_leaf(x, key, np.uint32(nonce_base + TREE_LEAF_STRIDE * i),
                        spec)
              for i, x in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, sealed)


def unseal_tree(tree, key: jax.Array):
    """Unseal every SealedTensor leaf.  Returns (tree, all_ok predicate)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_sealed)
    outs, oks = [], []
    for leaf in leaves:
        if is_sealed(leaf):
            x, ok = unseal(leaf, key)
            outs.append(x)
            oks.append(ok)
        else:
            outs.append(leaf)
    all_ok = jnp.stack(oks).all() if oks else jnp.bool_(True)
    return jax.tree_util.tree_unflatten(treedef, outs), all_ok


def reseal_tree(sealed_old, new_tree, key: jax.Array):
    """Reseal a plaintext tree into existing sealed slots (nonce bump)."""
    olds, treedef = jax.tree_util.tree_flatten(sealed_old, is_leaf=is_sealed)
    news = treedef.flatten_up_to(new_tree)
    out = [reseal(o, n, key) if is_sealed(o) else n for o, n in zip(olds, news)]
    return jax.tree_util.tree_unflatten(treedef, out)
