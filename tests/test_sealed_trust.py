"""SealedTensor invariants + trust establishment + Rule-3 registers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sealed, trust
from repro.core.policy import Protection, SealedSpec
from repro.core.registers import (DeviceRegisterFile, HostRegisterFile,
                                  ReplayError, TamperError)


def test_seal_unseal_tree_and_tamper(key):
    spec = SealedSpec(chunk_words=128)
    params = {"w": jnp.ones((16, 128), jnp.bfloat16),
              "b": jnp.zeros((128,), jnp.float32)}
    stree = sealed.seal_tree(params, key, spec)
    out, ok = jax.jit(lambda t: sealed.unseal_tree(t, key))(stree)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(out["w"], np.float32), 1.0)
    # tamper
    st = stree["w"]
    stree["w"] = sealed.SealedTensor(st.ct.at[0, 0].add(1), st.tags, st.nonce,
                                     st.dtype, st.spec)
    _, ok2 = sealed.unseal_tree(stree, key)
    assert not bool(ok2)


def test_seal_tree_donating_consumes_plaintext_and_matches(key):
    spec = SealedSpec(chunk_words=128)

    def make():
        return {"w": jnp.arange(16 * 128, dtype=jnp.float32)
                .reshape(16, 128).astype(jnp.bfloat16),
                "b": jnp.full((128,), 3.0, jnp.float32)}

    kept = sealed.seal_tree(make(), key, spec, nonce_base=7)
    params = make()
    donated = sealed.seal_tree(params, key, spec, nonce_base=7, donate=True)
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(params))
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(donated)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out, ok = sealed.unseal_tree(donated, key)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(out["w"], np.float32),
                                  np.asarray(make()["w"], np.float32))


def test_replay_detected_via_nonce_binding(key):
    spec = SealedSpec(chunk_words=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 256), jnp.float32)
    st = sealed.seal(x, key, 5, spec)
    replayed = sealed.SealedTensor(st.ct, st.tags, st.nonce + 1, st.dtype,
                                   st.spec)
    _, ok = sealed.unseal(replayed, key)
    assert not bool(ok)


def test_reseal_bumps_nonce(key):
    spec = SealedSpec(chunk_words=64)
    x = jnp.ones((4, 64), jnp.float32)
    st = sealed.seal(x, key, 1, spec)
    st2 = sealed.reseal(st, x * 2, key)
    assert int(st2.nonce) == int(st.nonce) + 1
    y, ok = sealed.unseal(st2, key)
    assert bool(ok) and float(y[0, 0]) == 2.0


def test_ctr_level_skips_tags(key):
    spec = SealedSpec(protection=Protection.CTR)
    st = sealed.seal(jnp.ones((4, 64), jnp.float32), key, 1, spec)
    assert st.tags.size == 0
    y, ok = sealed.unseal(st, key)
    assert bool(ok)


def test_trust_handshake_and_key_agreement():
    host, accel, kw = trust.establish_session("dev-1")
    assert host.session_key == accel.session_key
    assert kw.dtype == np.uint32 and kw.shape == (2,)


def test_attestation_rejects_unknown_device():
    ca = trust.ManufacturerCA()
    genuine = trust.TrustedAccelerator("dev-a", ca)
    rogue = trust.TrustedAccelerator("dev-b", trust.ManufacturerCA())  # other CA
    host = trust.HostProgram(ca)
    host.establish(genuine)
    with pytest.raises(trust.SecurityError):
        host.establish(rogue)


def test_schnorr_rejects_forgery():
    kp = trust.keygen()
    sig = trust.sign(kp.sk, b"hello")
    assert trust.verify(kp.pk, b"hello", sig)
    assert not trust.verify(kp.pk, b"hellp", sig)
    assert not trust.verify(kp.pk, b"hello", (sig[0], sig[1] + 1))


def test_register_rule3_tamper_and_replay():
    kb = b"k" * 32
    host = HostRegisterFile(key=kb)
    dev = DeviceRegisterFile(key=kb)
    state, nonce, tag = host.write(addr=0x1000, len=64)
    dev.commit(state, nonce, tag)
    # replay
    with pytest.raises(ReplayError):
        dev.commit(state, nonce, tag)
    # tamper by the untrusted driver
    state2, nonce2, tag2 = host.write(addr=0x2000)
    evil = dict(state2, addr=0xDEAD)
    with pytest.raises(TamperError):
        dev.commit(evil, nonce2, tag2)
    dev.commit(state2, nonce2, tag2)
