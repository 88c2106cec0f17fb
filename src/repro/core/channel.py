"""SecureChannel — the end-to-end secure offload path (paper Figure 1/3).

Ties together the substrate:
  trust.py      -> session key K between enclave and accelerator
  sealed.py     -> Rules 1 & 2: code/data sealed in untrusted memory
  registers.py  -> Rule 3: launch-descriptor MAC + nonce via the untrusted driver

``SecureChannel.launch`` is the JAX analogue of "runtime writes registers, then
the MAC register, then the driver kicks the accelerator": it MACs the launch
descriptor, the device register file verifies it, then the jitted step runs
over sealed operands and gates its outputs on the in-graph verification
predicate (a tampered operand poisons the result with NaNs instead of silently
computing on attacker-controlled data).
"""
from __future__ import annotations

import dataclasses
import hashlib
import hmac
import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import sealed as sealed_lib
from . import trust
from .policy import SealedSpec, SecurityConfig
from .registers import DeviceRegisterFile, HostRegisterFile

# ---------------------------------------------------------------------------
# Nonce domain separation.
#
# A sealing nonce is a 32-bit word structured as (session-id, epoch, counter):
#
#     bits 24..31   session id   (per-SecureChannel, process-unique)
#     bits 16..23   key epoch    (bumped by rekey/rotation and on counter wrap)
#     bits  0..15   counter      (monotone within an epoch; spans reservable)
#
# Two channels therefore can never collide on a (key, nonce) pair even if they
# were (mis)configured with the same key: their session-id lanes differ.  The
# old implementation was a bare Python counter with a fixed stride — identical
# keys in two channels silently reused counter space.
# ---------------------------------------------------------------------------

_COUNTER_BITS = 16
_EPOCH_BITS = 8
_SESSION_BITS = 8
_COUNTER_SPACE = 1 << _COUNTER_BITS
_EPOCH_SPACE = 1 << _EPOCH_BITS
_session_ids = itertools.count(1)

# per-leaf nonce stride used by sealed.seal_tree — reseal() may bump each
# leaf's nonce up to stride-1 times before lanes would touch.  The counting
# guard that enforces this budget lives next to the stride (core/sealed.py).
TREE_LEAF_STRIDE = sealed_lib.TREE_LEAF_STRIDE


def wrap_key_words(key_words: np.ndarray, wrap_key_bytes: bytes,
                   context: bytes) -> bytes:
    """Wrap a uint32[2] page key to another principal's control-plane key.

    The pad is HMAC(wrap_key, "key-wrap-v1" | context) truncated to the key
    width, XORed over the raw key bytes.  Only the holder of
    ``wrap_key_bytes`` (e.g. a tenant's session HMAC key) can unwrap; a
    different tenant's key, or the right key with the wrong context, yields
    garbage words — and sealed pages unsealed under garbage words fail their
    MACs and poison.  Context binds the wrap to one (prefix, tenant) pair so
    wraps are not transplantable across prefixes.
    """
    raw = np.asarray(key_words, np.uint32).tobytes()
    pad = hmac.new(wrap_key_bytes, b"key-wrap-v1|" + context,
                   hashlib.sha256).digest()[:len(raw)]
    return bytes(a ^ b for a, b in zip(raw, pad))


def unwrap_key_words(wrapped: bytes, wrap_key_bytes: bytes,
                     context: bytes) -> np.ndarray:
    """Inverse of :func:`wrap_key_words`; returns uint32[2] key words."""
    pad = hmac.new(wrap_key_bytes, b"key-wrap-v1|" + context,
                   hashlib.sha256).digest()[:len(wrapped)]
    raw = bytes(a ^ b for a, b in zip(wrapped, pad))
    return np.frombuffer(raw, np.uint32).copy()


def poison_unless(ok: jax.Array, tree):
    """Gate a pytree of outputs on a verification predicate.

    ok=False => every float leaf becomes NaN, every int leaf becomes the
    sentinel minimum.  This is the software analogue of the accelerator
    refusing to use unauthenticated data: nothing useful leaves the device.
    """
    def gate(x):
        if not isinstance(x, jax.Array) and not hasattr(x, "dtype"):
            return x
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.where(ok, x, jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.integer):
            return jnp.where(ok, x, jnp.iinfo(x.dtype).min)
        return x
    return jax.tree_util.tree_map(gate, tree)


@dataclasses.dataclass
class SecureChannel:
    """Host <-> accelerator channel over untrusted memory and an untrusted driver."""
    key_words: np.ndarray           # uint32[2] data-plane cipher key
    key_bytes: bytes                # control-plane HMAC key (Rule 3)
    config: SecurityConfig
    host_regs: HostRegisterFile = None
    device_regs: DeviceRegisterFile = None
    session_id: int = 0             # 0 => auto-assign a process-unique id
    epoch: int = 0                  # key epoch (bumped by rekey / wrap)
    _nonce_counter: int = 0
    audit: Any = None               # obs.AuditLog; records launch verdicts
    audit_tenant: str | None = None  # tenant attribution for audit records

    def __post_init__(self):
        if not self.session_id:
            self.session_id = next(_session_ids)
        if self.session_id >= (1 << _SESSION_BITS):
            # a wrapped lane would silently collide with an earlier channel's
            # (key, nonce) space — refuse, like epoch exhaustion does
            raise trust.SecurityError(
                "session-id space exhausted (max "
                f"{(1 << _SESSION_BITS) - 1} channels per process)")

    @classmethod
    def establish(cls, config: SecurityConfig | None = None, device_id: str = "tpu-0"):
        """Run the full paper §3.2 handshake and open a channel."""
        config = config or SecurityConfig()
        host, accel, key_words = trust.establish_session(device_id)
        kb = host.session_key
        return cls(key_words=key_words, key_bytes=kb, config=config,
                   host_regs=HostRegisterFile(key=kb),
                   device_regs=DeviceRegisterFile(key=kb))

    @classmethod
    def insecure(cls, config: SecurityConfig | None = None):
        """Protection.NONE channel for baselines (the paper's plain-VTA row)."""
        config = config or SecurityConfig.off()
        kb = b"\x00" * 32
        kw = np.zeros((2,), np.uint32)
        return cls(key_words=kw, key_bytes=kb, config=config,
                   host_regs=HostRegisterFile(key=kb),
                   device_regs=DeviceRegisterFile(key=kb))

    # -- data plane -----------------------------------------------------
    @property
    def jkey(self) -> jax.Array:
        return jnp.asarray(self.key_words, jnp.uint32)

    def subkey(self, domain: int) -> jax.Array:
        """Domain-separated data-plane subkey (e.g. the KV-cache lane)."""
        from . import cipher
        return cipher.derive_key(self.jkey, domain)

    def bump_epoch(self) -> None:
        self.epoch += 1
        self._nonce_counter = 0
        if self.epoch >= _EPOCH_SPACE:
            raise trust.SecurityError(
                "nonce epoch space exhausted — rotate the session key")

    def advance_epoch(self, floor: int) -> None:
        """Raise the key epoch to at least ``floor`` (freshness floor).

        Used when restoring warm state: a restarted session must never
        re-walk nonce lanes a previous incarnation already spent, so the
        epoch jumps past the last persisted one.  No-op if already past.
        """
        if self.epoch >= floor:
            return
        if floor >= _EPOCH_SPACE:
            raise trust.SecurityError(
                "nonce epoch space exhausted — rotate the session key")
        self.epoch = floor
        self._nonce_counter = 0

    def fresh_nonce(self, span: int = 1) -> int:
        """Reserve ``span`` consecutive counter slots; return the first nonce.

        Nonces are (session-id, epoch, counter) — see the module header.  A
        span that would cross the counter boundary rolls into a fresh epoch,
        so a reservation is always contiguous and never reused.
        """
        span = max(1, int(span))
        if span > _COUNTER_SPACE:
            raise trust.SecurityError(
                f"nonce span {span} exceeds the per-epoch counter space; "
                "seal in smaller trees or rotate more often")
        if self._nonce_counter + span > _COUNTER_SPACE:
            self.bump_epoch()
        base = self._nonce_counter
        self._nonce_counter += span
        return ((self.session_id & ((1 << _SESSION_BITS) - 1)) << 24
                | (self.epoch & (_EPOCH_SPACE - 1)) << 16
                | base)

    def restore_register_floor(self, last_nonce: int) -> None:
        """Rule-3 warm restart: raise the register-file nonce floor.

        A restarted gateway's device register file would otherwise start at
        0 and accept *any* forward nonce — including a replayed pre-restart
        launch stream.  Restoring the last verified launch nonce from warm
        state makes pre-restart nonces stale on the device side, exactly as
        if the process had never died.  Monotone: never lowers the floor.
        """
        floor = max(0, int(last_nonce))
        if self.host_regs is not None:
            self.host_regs.nonce = max(self.host_regs.nonce, floor)
        if self.device_regs is not None:
            self.device_regs.last_nonce = max(self.device_regs.last_nonce,
                                              floor)

    def rekey(self, key_words: np.ndarray, key_bytes: bytes) -> None:
        """Install a rotated session key (new handshake material).

        Bumps the epoch so nonces from the old key's lifetime are never
        replayed against the new key, and re-keys the Rule-3 register path.
        Sealed state from before the rotation must be re-sealed by the owner —
        this is enforced by callers (the gateway rotates only idle tenants).
        """
        self.key_words = key_words
        self.key_bytes = key_bytes
        self.bump_epoch()
        last = self.device_regs.last_nonce if self.device_regs else 0
        self.host_regs = HostRegisterFile(key=key_bytes, nonce=last)
        self.device_regs = DeviceRegisterFile(key=key_bytes, last_nonce=last)

    def upload(self, x: jax.Array, spec: SealedSpec | None = None):
        """Host -> untrusted HBM: seal a tensor (Rule 1)."""
        spec = spec or self.config.weights
        return sealed_lib.seal(x, self.jkey, self.fresh_nonce(span=TREE_LEAF_STRIDE),
                               spec)

    def upload_tree(self, tree, spec: SealedSpec | None = None, *,
                    donate: bool = False):
        """Seal a whole tree (Rule 1); ``donate`` consumes the plaintext
        leaves (sealed.seal_tree)."""
        spec = spec or self.config.weights
        n_leaves = len(jax.tree_util.tree_leaves(tree))
        span = TREE_LEAF_STRIDE * (n_leaves + 1)
        return sealed_lib.seal_tree(tree, self.jkey, spec,
                                    self.fresh_nonce(span=span), donate=donate)

    def refresh_tree(self, sealed_tree, spec: SealedSpec | None = None):
        """Re-seal a tree under fresh nonce lanes (epoch bump).

        The escape hatch the reseal-count guard (sealed.ResealCounter) forces
        before per-leaf lanes can touch: verify + decrypt every leaf, bump to
        a fresh epoch, and seal again with brand-new leaf lanes.  Raises on
        integrity failure — a tampered tree is never re-signed.
        """
        spec = spec or self.config.weights
        tree, ok = sealed_lib.unseal_tree(sealed_tree, self.jkey)
        if not bool(ok):
            raise trust.SecurityError(
                "refresh_tree: sealed tree failed integrity verification")
        self.bump_epoch()
        return self.upload_tree(tree, spec)

    def download(self, st) -> jax.Array:
        """Untrusted HBM -> host enclave: unseal + verify (strict)."""
        x, ok = sealed_lib.unseal(st, self.jkey)
        if not bool(ok):
            raise trust.SecurityError("download integrity check failed")
        return x

    # -- launch path (Rule 3) --------------------------------------------
    def launch(self, step_fn: Callable, descriptor: dict[str, Any], *args, **kwargs):
        """Protected dispatch: MAC the descriptor, verify on 'device', run."""
        if self.config.protect_launch:
            state, nonce, tag = self.host_regs.write(**descriptor)
            # the untrusted driver would carry (state, nonce, tag) via MMIO;
            # the device-side register file verifies before the core starts.
            try:
                self.device_regs.commit(state, nonce, tag)
            except Exception as e:
                if self.audit is not None:
                    self.audit.append("launch_reject",
                                      tenant=self.audit_tenant,
                                      op=str(descriptor.get("op")),
                                      nonce=int(nonce),
                                      error=type(e).__name__)
                raise
            if self.audit is not None:
                self.audit.append("launch", tenant=self.audit_tenant,
                                  op=str(descriptor.get("op")),
                                  nonce=int(nonce))
        return step_fn(*args, **kwargs)
