"""The benchmark's reference against published known answers: the curve's
parameters, the generators' standard encodings, and RFC 9380's test vectors
for expand_message_xmd (appendix K.1) and hash-to-G2 (appendix J.10.1). A
wrong field, isogeny or cofactor constant fails these."""

import pytest

from reference import ref

QUUX_G2 = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
QUUX_XMD = b"QUUX-V01-CS02-with-expander-SHA256-128"


def test_field_and_group_order_from_the_curve_parameter():
    assert ref.P == int(
        "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241e"
        "abfffeb153ffffb9feffffffffaaab", 16)
    assert ref.R == int(
        "73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001", 16)


@pytest.mark.parametrize("F,b,g,enc", [
    (ref.Fp, ref.B1, ref.G1, ref.g1_compress),
    (ref.Fp2, ref.B2, ref.G2, ref.g2_compress)], ids=("G1", "G2"))
def test_generators_lie_on_the_curve_with_order_r(F, b, g, enc):
    assert ref.on_curve(F, b, ref.to_affine(F, g))
    assert ref.is_inf(F, ref.mul(F, g, ref.R))
    assert not ref.is_inf(F, ref.mul(F, g, ref.R - 1))
    assert enc(g).hex() in (ref.G1_HEX, ref.G2_HEX)


@pytest.mark.parametrize("msg,want", [
    (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
    (b"abc",
     "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615")])
def test_expand_message_xmd_rfc9380_k1(msg, want):
    assert ref.expand_message_xmd(msg, QUUX_XMD, 0x20).hex() == want


def test_hash_to_g2_rfc9380_j10_1_empty_message():
    x, y = ref.to_affine(ref.Fp2, ref.hash_to_g2(b"", QUUX_G2))
    assert x == (
        0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
        0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D)
    assert y == (
        0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
        0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6)


def test_hash_to_g2_rfc9380_j10_1_abc():
    x, _y = ref.to_affine(ref.Fp2, ref.hash_to_g2(b"abc", QUUX_G2))
    assert x == (
        0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
        0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8)


def test_hash_output_lies_in_g2():
    h = ref.hash_to_g2(b"\x05" * 32)
    assert ref.on_curve(ref.Fp2, ref.B2, ref.to_affine(ref.Fp2, h))
    assert ref.is_inf(ref.Fp2, ref.mul(ref.Fp2, h, ref.R))


def test_fixed_base_keys_and_signatures_equal_plain_multiples():
    m = b"\x09" * 32
    for sk in (1, 2, 0xFF, 0x100, 0x1234567890ABCDEF, ref.R - 1):
        pk, sig = ref.pubkey(sk), ref.sign(sk, m)
        assert pk == ref.g1_compress(ref.mul(ref.Fp, ref.G1, sk))
        assert sig == ref.g2_compress(ref.mul(ref.Fp2, ref.hash_to_g2(m), sk))
        assert ref.g1_compress(ref.g1_decompress(pk)) == pk
        assert ref.g2_compress(ref.g2_decompress(sig)) == sig
    assert ref.sign_many(m, [3, 4]) == [ref.sign(3, m), ref.sign(4, m)]


def test_uniqueness_rule_accepts_only_the_signers_signature_of_the_root():
    sk, m = 0x1234567, b"\x01" * 32
    assert ref.verifies(ref.sign(sk, m), sk, m)
    assert not ref.verifies(ref.sign(sk + 1, m), sk, m)
    assert not ref.verifies(ref.sign(sk, b"\x02" * 32), sk, m)
