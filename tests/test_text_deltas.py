"""`llm/serving.text_deltas`: a stream of token ids becomes text deltas by
decoding a short window of ids a token, never the whole answer. Held here
for every tokenizer the tree has (`ByteTokenizer`, the cells' `IdTokenizer`)
and for a toy one whose `decode` looks at the left neighbour: the deltas'
concatenation is `decode(all ids)`, nothing yielded is ever taken back, an
id whose text is complete is yielded in the turn it is taken, and the ids
handed to `decode` a token do not grow with the answer. No JAX, no cluster.
"""

import pytest

from benchmarks.replica import IdTokenizer
from ray_tpu.llm.config import ByteTokenizer
from ray_tpu.llm.serving import text_deltas

INCOMPLETE = "�"


class _PieceTokenizer:
    """The shape of a sentencepiece vocabulary: a piece that opens a word
    starts with "▁", `decode` drops the leading space of the FIRST piece it
    is given, and a character the vocabulary lacks is spelled as one
    byte-fallback piece a byte, which only together decode to it."""

    PIECES = ["▁Hello", "▁wor", "ld", "▁", "!", "▁ok", "<0xC3>", "<0xA9>",
              "<0xE2>", "<0x82>", "<0xAC>", "<0xF0>", "<0x9F>", "<0x98>",
              "<0x80>"]

    def encode(self, pieces):
        return [self.PIECES.index(p) for p in pieces.split("|")]

    def decode(self, ids) -> str:
        out = bytearray()
        for i in ids:
            piece = self.PIECES[i]
            if piece.startswith("<0x"):
                out.append(int(piece[3:5], 16))
            else:
                out += piece.replace("▁", " ").encode()
        text = out.decode("utf-8", errors="replace")
        return text[1:] if text.startswith(" ") else text


class _TrailingSpace(IdTokenizer):
    """Every id's text ends in its separator: no delta starts with one."""

    def decode(self, ids) -> str:
        return "".join(f"{int(i)} " for i in ids)


class _Counting:
    """`decode` of another tokenizer, with the ids of each call counted."""

    def __init__(self, tokenizer):
        self.tokenizer = tokenizer
        self.calls = []

    def decode(self, ids) -> str:
        self.calls.append(len(ids))
        return self.tokenizer.decode(ids)


def _bytes(text, cut=0):
    ids = ByteTokenizer().encode(text)
    return ids[:len(ids) - cut]


EURO = _PieceTokenizer().encode("▁|<0xE2>|<0x82>|<0xAC>")
CASES = {
    "bytes-ascii": (ByteTokenizer(), _bytes("plain ASCII, one id a character")),
    "bytes-2-byte": (ByteTokenizer(), _bytes("né à côté, déjà")),
    "bytes-3-byte": (ByteTokenizer(), _bytes("10 € 或者 20 €")),
    "bytes-4-byte": (ByteTokenizer(), _bytes("a 𝄞 and 😀😀 b")),
    "bytes-ends-mid-2": (ByteTokenizer(), _bytes("café", cut=1)),
    "bytes-ends-mid-3": (ByteTokenizer(), _bytes("5 €", cut=2)),
    "bytes-ends-mid-4": (ByteTokenizer(), _bytes("ok \U0001f600", cut=1)),
    # a byte no character holds, between characters and before the EOS id
    "bytes-invalid": (ByteTokenizer(), [104, 0xFF, 105, 0x80, 0xE2, 0x82,
                                        0xAC, 33, 0xC3, 256]),
    # the replacement character itself, spelled in full, is text
    "bytes-real-fffd": (ByteTokenizer(), _bytes("a � b �")),
    "ids-decimal": (IdTokenizer(), [7, 262271, 0, 15, 15, 1024, 3]),
    "ids-trailing-space": (_TrailingSpace(), [1, 22, 333, 4]),
    "pieces-leading-space": (_PieceTokenizer(), _PieceTokenizer().encode(
        "▁Hello|▁wor|ld|!|▁ok|▁|▁ok")),
    "pieces-byte-fallback": (_PieceTokenizer(), _PieceTokenizer().encode(
        "▁Hello|▁|<0xC3>|<0xA9>|ld|▁|<0xE2>|<0x82>|<0xAC>|▁ok|"
        "<0xF0>|<0x9F>|<0x98>|<0x80>|!")),
    "pieces-open-with-bytes": (_PieceTokenizer(), EURO + EURO[1:]),
    "pieces-ends-mid": (_PieceTokenizer(), _PieceTokenizer().encode(
        "▁ok|▁|<0xF0>|<0x9F>|<0x98>")),
}


def _turns(tokenizer, ids):
    """(per taken id, the text yielded in its turn; the text flushed at the
    stream's end): `text_deltas` takes the next id only after it has yielded
    the delta of the one before, so a delta belongs to the id taken last."""
    taken = []

    def stream():
        for t in ids:
            taken.append(t)
            yield t
        taken.append(None)  # the stream has ended: what comes now is flushed

    by_turn, flushed = [""] * len(ids), ""
    for delta in text_deltas(tokenizer, stream()):
        assert delta, "an empty delta was yielded"
        if taken[-1] is None:
            flushed += delta
        else:
            by_turn[len(taken) - 1] += delta
    return by_turn, flushed


@pytest.mark.parametrize("case", sorted(CASES))
def test_deltas_are_the_whole_decode_never_taken_back_and_not_late(case):
    tokenizer, ids = CASES[case]
    whole = tokenizer.decode(ids)
    by_turn, flushed = _turns(tokenizer, ids)
    assert "".join(by_turn) + flushed == whole
    sent = ""
    for n, delta in enumerate(by_turn, 1):
        sent += delta
        # what has been sent stays: the final text begins with it
        assert whole.startswith(sent), (n, sent)
        so_far = tokenizer.decode(ids[:n])
        if not so_far.endswith(INCOMPLETE):
            # the answer so far is complete text: all of it has been sent in
            # this id's own turn, none of it waits for the next id
            assert sent == so_far, (n, sent, so_far)
        else:
            assert so_far.startswith(sent) and INCOMPLETE not in delta[-1:]
    # only an answer that ends inside a character has anything to flush
    assert bool(flushed) == whole.endswith(INCOMPLETE)


@pytest.mark.parametrize("case, most_a_turn", [
    ("ids", 3),  # the id before, twice, and the new one
    ("bytes-4-byte", 12),  # a held character of 4 behind one of 4: 4 + 8
])
def test_ids_handed_to_decode_do_not_grow_with_the_answer(case, most_a_turn):
    n = 1024
    if case == "ids":
        inner, ids = IdTokenizer(), [(i * 7919) % 262272 for i in range(n)]
    else:
        inner, ids = ByteTokenizer(), _bytes("\U0001f600" * (n // 4))
    tokenizer = _Counting(inner)
    assert "".join(text_deltas(tokenizer, iter(ids))) == inner.decode(ids)
    assert len(ids) == n and max(tokenizer.calls) <= most_a_turn
    # the whole answer again for every id would be n (n + 1) / 2 = 524,800
    assert sum(tokenizer.calls) <= most_a_turn * n


def test_the_window_grows_only_while_a_character_is_held():
    tokenizer = _Counting(ByteTokenizer())
    # 200 continuation bytes complete nothing: held, every one of them
    ids = _bytes("a") + [0x80] * 200 + _bytes("b" * 200)
    assert "".join(text_deltas(tokenizer, iter(ids))) == \
        ByteTokenizer().decode(ids)
    assert max(tokenizer.calls) == 202  # "a" + the 200 + "b"
    assert max(tokenizer.calls[-100:]) <= 2  # and short again behind them
