"""`reference.py` against `hashlib`, RFC 1320's MD4 vectors, and the
mask order the configurations state."""

import hashlib

import pytest

import reference

RFC1320 = {
    b"": "31d6cfe0d16ae931b73c59d7e0c089c0",
    b"a": "bde52cb31de33e46245e05fbdbd6fb24",
    b"abc": "a448017aaf21d8525fc10ae87aa6729d",
    b"message digest": "d9130a8164549fe818874806e1c7014b",
    b"abcdefghijklmnopqrstuvwxyz": "d79e1c308aa5bbcdeea8ed63df412da9",
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789":
        "043f8582f241db351ce627e153e7f0e4",
    b"1234567890" * 8: "e33b4ddc9c38f2199c3e7b164fcc0536",
}


@pytest.mark.parametrize("msg", list(RFC1320))
def test_md4_rfc1320(msg):
    assert reference.md4(msg).hex() == RFC1320[msg]


def test_ntlm_known():
    assert reference.ntlm(b"password").hex() == \
        "8846f7eaee8fb117ad06bdd830b7586c"


def test_md5_is_hashlib():
    assert reference.md5(b"password") == hashlib.md5(b"password").digest()


def test_mask_order_rightmost_fastest():
    assert reference.candidate("?l?l?l", 0) == b"aaa"
    assert reference.candidate("?l?l?l", 1) == b"aab"
    assert reference.candidate("?l?l?l", 26) == b"aba"
    assert reference.keyspace("?a?a") == 95 * 95
    assert reference.candidate("?a?a", 95 * 95 - 1) == b"~~"
    assert reference.candidate("x?d??", 7) == b"x7?"
    with pytest.raises(ValueError):
        reference.candidate("?d", 10)


@pytest.mark.parametrize("mask", ["?l?l?l", "?a?a?a", "?d?u?s?l"])
def test_mask_agrees_with_the_program(mask):
    from dprf_tpu.generators.mask import MaskGenerator
    gen = MaskGenerator(mask)
    assert gen.keyspace == reference.keyspace(mask)
    for i in (0, 1, 97, gen.keyspace // 2, gen.keyspace - 1):
        assert gen.candidate(i) == reference.candidate(mask, i)


def test_potfile_hex_plain(tmp_path):
    p = tmp_path / "pot"
    p.write_text("aa:abc\nbb:$HEX[3a41]\n")
    assert reference.read_potfile(str(p)) == [("aa", b"abc"),
                                              ("bb", b":A")]
