"""Security layer: OTP involution, MAC soundness (vs python-int oracle),
fernet-lite AEAD (TTL / clock skew / truncation / bit flips / batch rows),
QKD key schedule, and secagg mask primitives (exact dropout recovery)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.security import (
    KeyManager, decrypt_tree, encrypt_tree, fernet_decrypt, fernet_encrypt,
    fernet_decrypt_rows, fernet_encrypt_rows, mac_verify, pairwise_mask_seed,
    poly_mac_u32, q32_to_tree, secagg_mask_stream, sum_signed_pads,
    tree_to_q32, tree_to_u32, u32_to_tree, SECAGG_FRAC_BITS,
)
from repro.security.fernet_lite import InvalidToken, TOKEN_OVERHEAD
from repro.security.keys import canonical_edge
from repro.security.mac import mulmod, addmod

P = 2**31 - 1

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:             # pragma: no cover - optional dev dep
    HAVE_HYPOTHESIS = False


def _tree(key):
    return {
        "a": jax.random.normal(key, (33,), jnp.float32),
        "b": jax.random.normal(key, (5, 7)).astype(jnp.bfloat16),
        "c": {"d": jax.random.normal(key, (3,), jnp.float32)},
    }


def test_otp_involution_and_diffusion(rng_key):
    tree = _tree(rng_key)
    enc = encrypt_tree(tree, jnp.uint32(99))
    dec = decrypt_tree(enc, jnp.uint32(99))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(dec)):
        assert bool(jnp.all(a == b))
    # ciphertext must differ and differ per key
    enc2 = encrypt_tree(tree, jnp.uint32(100))
    assert bool(jnp.any(enc["a"] != tree["a"]))
    assert bool(jnp.any(enc["a"] != enc2["a"]))


def test_wrong_key_garbles(rng_key):
    tree = _tree(rng_key)
    dec = decrypt_tree(encrypt_tree(tree, jnp.uint32(1)), jnp.uint32(2))
    assert bool(jnp.any(dec["a"] != tree["a"]))


def test_u32_view_roundtrip(rng_key):
    tree = _tree(rng_key)
    flat = tree_to_u32(tree)
    back = u32_to_tree(flat, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert bool(jnp.all(a == b))


def test_mac_python_oracle():
    rng = np.random.default_rng(0)
    msg = rng.integers(0, 2**32, 257, dtype=np.uint32)
    r_key, s_key = 777, 888
    tag = int(poly_mac_u32(jnp.asarray(msg), jnp.uint32(r_key),
                           jnp.uint32(s_key)))
    # independent python-int implementation
    r = (r_key % P) | 1
    s = s_key % P
    syms = []
    for w in msg.tolist():
        syms += [(w & 0xFFFF) + 1, (w >> 16) + 1]
    n = len(syms)
    acc = 0
    for i, m in enumerate(syms):
        acc = (acc + m * pow(r, n - i, P)) % P
    expect = (acc + (n % P) * s) % P
    assert tag == expect


# ---------------------------------------------------------------------------
# fernet-lite: token structure edge cases + batched rows
# ---------------------------------------------------------------------------

def test_fernet_roundtrip_and_ttl():
    key = b"0" * 32
    tok = fernet_encrypt(key, b"telemetry", now=1000.0)
    assert fernet_decrypt(key, tok, now=1001.0) == b"telemetry"
    with pytest.raises(InvalidToken):
        fernet_decrypt(key, tok, ttl=5.0, now=2000.0)
    with pytest.raises(InvalidToken):
        bad = tok[:-1] + bytes([tok[-1] ^ 1])
        fernet_decrypt(key, bad)
    with pytest.raises(InvalidToken):
        fernet_decrypt(b"1" * 32, tok)


def test_fernet_clock_skew():
    """A receiver clock slightly behind the sender is tolerated; a token
    time-stamped beyond the skew window is rejected as from-the-future."""
    key = b"0" * 32
    tok = fernet_encrypt(key, b"m", now=1000.0)
    # receiver 30 s behind: inside the 60 s default skew, even with a ttl
    assert fernet_decrypt(key, tok, ttl=5.0, now=970.0) == b"m"
    with pytest.raises(InvalidToken):
        fernet_decrypt(key, tok, now=1000.0 - 61.0)
    # skew enforcement can be relaxed explicitly
    assert fernet_decrypt(key, tok, now=100.0, max_clock_skew=None) == b"m"


def test_fernet_truncated_and_flipped_tokens():
    key = b"k" * 32
    tok = fernet_encrypt(key, b"payload", now=5.0)
    assert len(tok) == TOKEN_OVERHEAD + len(b"payload")
    # truncation anywhere -> clean failure, never garbage plaintext
    for cut in (0, 1, 8, 25, len(tok) - 33, len(tok) - 1):
        with pytest.raises(InvalidToken):
            fernet_decrypt(key, tok[:cut])
    # a flipped bit anywhere in the token fails the MAC (or the version)
    for pos in (0, 3, 12, 30, len(tok) - 40, len(tok) - 2):
        bad = bytearray(tok)
        bad[pos] ^= 0x10
        with pytest.raises(InvalidToken):
            fernet_decrypt(key, bytes(bad))


def test_fernet_empty_plaintext():
    key = b"e" * 32
    tok = fernet_encrypt(key, b"", now=9.0)
    assert len(tok) == TOKEN_OVERHEAD
    assert fernet_decrypt(key, tok, now=9.5) == b""


def test_fernet_rows_match_scalar_loop():
    """Batch entries are byte-for-byte the scalar loop (pinned ivs/now)."""
    keys = [bytes([i]) * 32 for i in range(5)]
    msgs = [f"edge={i} round={i % 3} n=128".encode() for i in range(4)]
    msgs.append(b"")                      # empty row rides along
    ivs = [bytes([i]) * 16 for i in range(5)]
    toks = fernet_encrypt_rows(keys, msgs, now=777.0, ivs=ivs)
    for k, m, iv, tok in zip(keys, msgs, ivs, toks):
        assert tok == fernet_encrypt(k, m, now=777.0, iv=iv)
    assert fernet_decrypt_rows(keys, toks, now=778.0) == msgs
    # one corrupt row aborts the whole stage call
    bad = list(toks)
    bad[2] = bad[2][:-1] + bytes([bad[2][-1] ^ 1])
    with pytest.raises(InvalidToken):
        fernet_decrypt_rows(keys, bad, now=778.0)


# ---------------------------------------------------------------------------
# key schedule
# ---------------------------------------------------------------------------

def test_key_manager_qber_gating(rng_key):
    km = KeyManager(rng_key, eavesdrop_edges=frozenset({(1, 2)}))
    clean = km.establish((3, 4))
    attacked = km.establish((1, 2))
    assert not clean.compromised
    assert attacked.compromised
    assert 1 in km.compromised_nodes() and 2 in km.compromised_nodes()
    # per-round seeds differ
    assert int(clean.round_seed(0)) != int(clean.round_seed(1))
    # rekey regenerates
    km2 = km.rekey((3, 4))
    assert km2.edge == (3, 4)


# ---------------------------------------------------------------------------
# secagg primitives: exact pairwise-mask cancellation + dropout recovery
# ---------------------------------------------------------------------------

def _f32_tree(key, scale=1.0):
    a, b = jax.random.split(key)
    return {"w": jax.random.normal(a, (11,)) * scale,
            "b": jax.random.normal(b, (3, 2)) * scale}


def test_quantize_roundtrip(rng_key):
    tree = _f32_tree(rng_key)
    q = tree_to_q32(tree)
    back = q32_to_tree(jax.lax.bitcast_convert_type(q, jnp.uint32), tree,
                       jnp.float32(1.0))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2.0 ** -SECAGG_FRAC_BITS)


def test_secagg_masks_cancel_and_recover_exactly(rng_key):
    """The acceptance property: a full cohort's masks cancel to zero, and
    a dropped satellite's pads are reconstructed and cancelled EXACTLY
    (bit-for-bit), leaving precisely the survivors' weighted aggregate."""
    km = KeyManager(rng_key)
    cohort = [2, 5, 7]
    born = 3
    wq = {2: 3, 5: 1, 7: 2}
    trees = {s: _f32_tree(jax.random.fold_in(rng_key, s)) for s in cohort}
    n_words = tree_to_q32(trees[2]).shape[0]
    pairs = [canonical_edge((a, b)) for a in cohort for b in cohort if a < b]
    base = km.share_edges(pairs)

    def masked(s):
        others = [x for x in cohort if x != s]
        seeds = jnp.asarray([pairwise_mask_seed(
            base[canonical_edge((s, o))], born) for o in others], jnp.uint32)
        signs = jnp.asarray([1 if s < o else -1 for o in others], jnp.int32)
        return secagg_mask_stream(trees[s], wq[s], seeds, signs)

    y = {s: masked(s) for s in cohort}

    def raw(s):
        return jax.lax.bitcast_convert_type(
            tree_to_q32(trees[s]) * jnp.int32(wq[s]), jnp.uint32)

    # full cohort: every pairwise pad cancels with its mirror
    full = y[2] + y[5] + y[7]
    assert bool(jnp.all(full == raw(2) + raw(5) + raw(7)))

    # satellite 7 drops out (QBER abort / missed window): survivors' pads
    # toward it linger — recover_masks cancels them to the bit
    agg = y[2] + y[5]
    corr = km.recover_masks(
        [canonical_edge((2, 7)), canonical_edge((5, 7))],
        [born, born], [-(1 if 2 < 7 else -1), -(1 if 5 < 7 else -1)],
        n_words)
    unmasked = agg + corr
    expect = raw(2) + raw(5)
    assert bool(jnp.all(unmasked == expect))
    # and WITHOUT recovery the aggregate is still fully masked
    assert bool(jnp.any(agg != expect))
    # dequantized survivors' FedAvg matches the float average
    merged = q32_to_tree(unmasked, trees[2], jnp.float32(wq[2] + wq[5]))
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(merged[k]),
            (3 * np.asarray(trees[2][k]) + np.asarray(trees[5][k])) / 4.0,
            atol=2e-4)


def test_sum_signed_pads_sign_convention():
    seeds = jnp.asarray([11, 11], jnp.uint32)
    out = sum_signed_pads(seeds, jnp.asarray([1, -1], jnp.int32), 16)
    assert bool(jnp.all(out == 0))        # +pad - pad == 0 mod 2^32
    zero = sum_signed_pads(seeds, jnp.asarray([0, 0], jnp.int32), 16)
    assert bool(jnp.all(zero == 0))       # sign 0 rows are skipped


def test_mask_domain_separation():
    """Mask pads never collide with the pair's OTP pad schedule."""
    from repro.security import round_seed_mix
    assert int(pairwise_mask_seed(1234, 7)) != int(round_seed_mix(1234, 7))


# ---------------------------------------------------------------------------
# property tests (optional hypothesis dependency)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @given(st.integers(0, P - 1), st.integers(0, P - 1))
    def test_mulmod_exact(a, b):
        got = int(mulmod(jnp.uint32(a), jnp.uint32(b)))
        assert got == (a * b) % P

    @given(st.integers(0, P - 1), st.integers(0, P - 1))
    def test_addmod_exact(a, b):
        assert int(addmod(jnp.uint32(a), jnp.uint32(b))) == (a + b) % P

    @given(st.integers(0, 256 * 2 - 1), st.integers(0, 31))
    def test_mac_detects_single_bitflip(pos, bit):
        msg = jax.random.bits(jax.random.key(7), (256,), jnp.uint32)
        r, s = jnp.uint32(123), jnp.uint32(456)
        tag = poly_mac_u32(msg, r, s)
        i = pos % 256
        tampered = msg.at[i].set(msg[i] ^ jnp.uint32(1 << bit))
        assert not bool(mac_verify(tampered, tag, r, s))
