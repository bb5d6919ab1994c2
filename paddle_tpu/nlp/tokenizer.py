"""Tokenizers (WordPiece + byte-level BPE-lite).

ref parity: PaddleNLP paddlenlp/transformers/bert/tokenizer.py
(BertTokenizer = BasicTokenizer + WordpieceTokenizer over a vocab file) and
paddlenlp/transformers/gpt/tokenizer.py (GPTTokenizer, byte-level BPE).
Pure Python host-side code — tokenization never enters the XLA program, so
there is no TPU-specific design here; the contract (encode -> dict of
input_ids/token_type_ids/attention_mask, pad/truncate, decode) matches the
reference so data pipelines port over unchanged.
"""
from __future__ import annotations

import collections
import re
import unicodedata

__all__ = ["BasicTokenizer", "WordpieceTokenizer", "BertTokenizer",
           "GPTTokenizer"]


# ---------------------------------------------------------------------------
# native fast path (csrc/pttok.cc): C++ basic-tokenize + wordpiece for
# ASCII/CJK text — the common pretraining-corpus case. Out-of-scope text
# (NFD accent stripping, unicode punctuation classes) returns -2 from the
# encoder and falls back to the Python reference implementation, so parity
# is exact by construction. ref role: paddlenlp fast_tokenizer (C++).
# ---------------------------------------------------------------------------
def _load_pttok():
    import ctypes

    from ..io.native import native_lib_path
    so = native_lib_path("pttok")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    lib.pttok_create.restype = ctypes.c_void_p
    lib.pttok_create.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                 ctypes.POINTER(ctypes.c_int), ctypes.c_int,
                                 ctypes.c_int, ctypes.c_int]
    lib.pttok_encode.restype = ctypes.c_int
    lib.pttok_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_long, ctypes.c_int,
                                 ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.pttok_destroy.argtypes = [ctypes.c_void_p]
    return lib


_PTTOK_LIB = None
_PTTOK_TRIED = False


def _pttok():
    global _PTTOK_LIB, _PTTOK_TRIED
    if not _PTTOK_TRIED:
        _PTTOK_TRIED = True
        _PTTOK_LIB = _load_pttok()
    return _PTTOK_LIB


def _is_punctuation(ch):
    cp = ord(ch)
    if (33 <= cp <= 47) or (58 <= cp <= 64) or (91 <= cp <= 96) \
            or (123 <= cp <= 126):
        return True
    return unicodedata.category(ch).startswith("P")


def _is_cjk(cp):
    return (0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF
            or 0x20000 <= cp <= 0x2A6DF or 0xF900 <= cp <= 0xFAFF)


class BasicTokenizer:
    """ref: bert/tokenizer.py BasicTokenizer — whitespace split, lowercase,
    accent strip, punctuation split, CJK char isolation."""

    def __init__(self, do_lower_case=True):
        self.do_lower_case = do_lower_case

    def tokenize(self, text):
        out = []
        spaced = []
        for ch in text:
            if _is_cjk(ord(ch)):
                spaced.append(f" {ch} ")
            else:
                spaced.append(ch)
        for tok in "".join(spaced).split():
            if self.do_lower_case:
                tok = tok.lower()
                tok = "".join(c for c in unicodedata.normalize("NFD", tok)
                              if unicodedata.category(c) != "Mn")
            out.extend(self._split_punc(tok))
        return out

    @staticmethod
    def _split_punc(tok):
        parts, cur = [], []
        for ch in tok:
            if _is_punctuation(ch):
                if cur:
                    parts.append("".join(cur))
                    cur = []
                parts.append(ch)
            else:
                cur.append(ch)
        if cur:
            parts.append("".join(cur))
        return parts


class WordpieceTokenizer:
    """ref: bert/tokenizer.py WordpieceTokenizer — greedy longest-match
    with '##' continuation prefix."""

    def __init__(self, vocab, unk_token="[UNK]", max_input_chars_per_word=100):
        self.vocab = vocab
        self.unk_token = unk_token
        self.max_input_chars_per_word = max_input_chars_per_word

    def tokenize(self, word):
        if len(word) > self.max_input_chars_per_word:
            return [self.unk_token]
        tokens, start = [], 0
        while start < len(word):
            end, cur = len(word), None
            while start < end:
                sub = word[start:end]
                if start > 0:
                    sub = "##" + sub
                if sub in self.vocab:
                    cur = sub
                    break
                end -= 1
            if cur is None:
                return [self.unk_token]
            tokens.append(cur)
            start = end
        return tokens


class BertTokenizer:
    """ref: BertTokenizer. vocab: path to one-token-per-line file, or a
    dict token->id, or an iterable of tokens."""

    SPECIALS = ("[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]")

    def __init__(self, vocab, do_lower_case=True, unk_token="[UNK]",
                 pad_token="[PAD]", cls_token="[CLS]", sep_token="[SEP]",
                 mask_token="[MASK]"):
        if isinstance(vocab, str):
            with open(vocab, encoding="utf-8") as f:
                vocab = [l.rstrip("\n") for l in f]
        if not isinstance(vocab, dict):
            vocab = {tok: i for i, tok in enumerate(vocab)}
        self.vocab = dict(vocab)
        for sp in self.SPECIALS:
            if sp not in self.vocab:
                self.vocab[sp] = len(self.vocab)
        self.ids_to_tokens = {i: t for t, i in self.vocab.items()}
        self.basic = BasicTokenizer(do_lower_case)
        self.wordpiece = WordpieceTokenizer(self.vocab, unk_token)
        self.unk_token, self.pad_token = unk_token, pad_token
        self.cls_token, self.sep_token = cls_token, sep_token
        self.mask_token = mask_token

    # -- vocab building (offline tool; the reference ships vocab files) ----
    @classmethod
    def from_corpus(cls, texts, vocab_size=8000, **kw):
        """Train a wordpiece-ish vocab: whole words by frequency, then
        suffix pieces, truncated to vocab_size."""
        basic = BasicTokenizer(kw.get("do_lower_case", True))
        counts = collections.Counter()
        for t in texts:
            counts.update(basic.tokenize(t))
        vocab = list(cls.SPECIALS)
        chars = sorted({c for w in counts for c in w})
        vocab += chars + ["##" + c for c in chars]
        seen = set(vocab)
        for w, _ in counts.most_common():
            if len(vocab) >= vocab_size:
                break
            if w not in seen:
                vocab.append(w)
                seen.add(w)
        return cls({t: i for i, t in enumerate(vocab[:vocab_size])}, **kw)

    @property
    def vocab_size(self):
        return len(self.vocab)

    def tokenize(self, text):
        out = []
        for word in self.basic.tokenize(text):
            out.extend(self.wordpiece.tokenize(word))
        return out

    # -- native fast path ---------------------------------------------------
    def _ensure_native(self):
        if getattr(self, "_native_handle", None) is not None:
            return self._native_handle
        if getattr(self, "_native_failed", False):
            return None
        lib = _pttok()
        if lib is None:
            self._native_failed = True
            return None
        # '\n'-joined tokens + parallel explicit id array (vocab ids can be
        # non-contiguous when built from a token list with duplicates)
        import ctypes
        if any("\n" in t for t in self.vocab):
            # a newline inside a token would corrupt the line-split buffer
            self._native_failed = True
            return None
        inv = sorted(self.vocab.items(), key=lambda kv: kv[1])
        buf = "\n".join(t for t, _ in inv).encode("utf-8")
        ids = (ctypes.c_int * len(inv))(*[i for _, i in inv])
        h = lib.pttok_create(buf, len(buf), ids, len(inv),
                             self.vocab[self.unk_token],
                             self.wordpiece.max_input_chars_per_word
                             if hasattr(self.wordpiece,
                                        "max_input_chars_per_word") else 100)
        if not h:
            self._native_failed = True
            return None
        self._native_lib = lib
        self._native_handle = h
        return h

    def text_to_ids(self, text):
        """Token ids for `text` (no specials) — C++ fast path for
        ASCII/CJK input, Python reference otherwise. Both produce
        identical output (tested)."""
        h = self._ensure_native()
        if h is not None:
            import ctypes
            raw = text.encode("utf-8")
            cap = max(64, 2 * len(raw) + 8)
            out = (ctypes.c_int * cap)()
            n = self._native_lib.pttok_encode(
                h, raw, len(raw), int(self.basic.do_lower_case), out, cap)
            while n == -1:  # output buffer too small (pathological input)
                cap *= 4
                out = (ctypes.c_int * cap)()
                n = self._native_lib.pttok_encode(
                    h, raw, len(raw), int(self.basic.do_lower_case), out,
                    cap)
            if n >= 0:
                return list(out[:n])
        return self.convert_tokens_to_ids(self.tokenize(text))

    def __del__(self):
        h = getattr(self, "_native_handle", None)
        if h is not None:
            try:
                self._native_lib.pttok_destroy(h)
            except Exception:
                pass

    def convert_tokens_to_ids(self, tokens):
        unk = self.vocab[self.unk_token]
        return [self.vocab.get(t, unk) for t in tokens]

    def convert_ids_to_tokens(self, ids):
        return [self.ids_to_tokens.get(int(i), self.unk_token) for i in ids]

    def encode(self, text, text_pair=None, max_length=None, padding=False,
               truncation=True):
        return self(text, text_pair, max_length=max_length, padding=padding,
                    truncation=truncation)

    def __call__(self, text, text_pair=None, max_length=None, padding=False,
                 truncation=True):
        a = self.text_to_ids(text)
        b = self.text_to_ids(text_pair) if text_pair else None
        cls_id, sep_id = self.vocab[self.cls_token], self.vocab[self.sep_token]
        if max_length and truncation:
            budget = max(max_length - (3 if b is not None else 2), 0)
            if b is not None:
                # longest-first truncation (ref truncate_sequences)
                while len(a) + len(b) > budget and (a or b):
                    (a if len(a) >= len(b) else b).pop()
            else:
                a = a[:budget]
        ids = [cls_id] + a + [sep_id]
        type_ids = [0] * len(ids)
        if b is not None:
            ids += b + [sep_id]
            type_ids += [1] * (len(b) + 1)
        mask = [1] * len(ids)
        if max_length and padding:
            pad_id = self.vocab[self.pad_token]
            pad_n = max_length - len(ids)
            ids += [pad_id] * pad_n
            type_ids += [0] * pad_n
            mask += [0] * pad_n
        return {"input_ids": ids, "token_type_ids": type_ids,
                "attention_mask": mask}

    def decode(self, ids, skip_special_tokens=True):
        toks = self.convert_ids_to_tokens(ids)
        if skip_special_tokens:
            toks = [t for t in toks if t not in self.SPECIALS]
        text = " ".join(toks).replace(" ##", "")
        return text


class GPTTokenizer:
    """Byte-level BPE (ref: gpt/tokenizer.py GPTTokenizer). Either load
    (vocab, merges) or train on a corpus with .train()."""

    def __init__(self, vocab=None, merges=None, unk_token="<|endoftext|>"):
        self.unk_token = unk_token
        self.vocab = dict(vocab) if vocab else {}
        self.merges = {tuple(m): i for i, m in enumerate(merges)} \
            if merges else {}
        if self.vocab:
            self.ids_to_tokens = {i: t for t, i in self.vocab.items()}

    @classmethod
    def train(cls, texts, vocab_size=1000, unk_token="<|endoftext|>"):
        """Classic BPE training: start from bytes, iteratively merge the
        most frequent adjacent pair."""
        words = collections.Counter()
        for t in texts:
            for w in re.findall(r"\S+\s*", t):
                words[tuple(w.encode("utf-8"))] += 1
        base = {bytes([i]).decode("latin-1"): i for i in range(256)}
        vocab = dict(base)
        vocab[unk_token] = len(vocab)
        words = {tuple(bytes([b]).decode("latin-1") for b in w): c
                 for w, c in words.items()}
        merges = []
        while len(vocab) < vocab_size:
            pairs = collections.Counter()
            for w, c in words.items():
                for i in range(len(w) - 1):
                    pairs[(w[i], w[i + 1])] += c
            if not pairs:
                break
            best = max(pairs, key=pairs.get)
            merged = best[0] + best[1]
            vocab[merged] = len(vocab)
            merges.append(best)
            new_words = {}
            for w, c in words.items():
                out, i = [], 0
                while i < len(w):
                    if i + 1 < len(w) and (w[i], w[i + 1]) == best:
                        out.append(merged)
                        i += 2
                    else:
                        out.append(w[i])
                        i += 1
                new_words[tuple(out)] = new_words.get(tuple(out), 0) + c
            words = new_words
        return cls(vocab, merges, unk_token)

    @property
    def vocab_size(self):
        return len(self.vocab)

    def _bpe(self, word):
        parts = [c for c in word]
        while len(parts) > 1:
            ranked = [(self.merges.get((parts[i], parts[i + 1]), None), i)
                      for i in range(len(parts) - 1)]
            ranked = [(r, i) for r, i in ranked if r is not None]
            if not ranked:
                break
            _, i = min(ranked)
            parts = parts[:i] + [parts[i] + parts[i + 1]] + parts[i + 2:]
        return parts

    def tokenize(self, text):
        out = []
        for w in re.findall(r"\S+\s*", text):
            latin = w.encode("utf-8").decode("latin-1")
            out.extend(self._bpe(latin))
        return out

    def encode(self, text):
        unk = self.vocab.get(self.unk_token, 0)
        return [self.vocab.get(t, unk) for t in self.tokenize(text)]

    def __call__(self, text, max_length=None, padding=False,
                 truncation=True):
        ids = self.encode(text)
        if max_length and truncation:
            ids = ids[:max_length]
        mask = [1] * len(ids)
        if max_length and padding:
            pad = self.vocab.get(self.unk_token, 0)
            mask += [0] * (max_length - len(ids))
            ids += [pad] * (max_length - len(ids))
        return {"input_ids": ids, "attention_mask": mask}

    def decode(self, ids):
        toks = [self.ids_to_tokens.get(int(i), "") for i in ids]
        return "".join(toks).encode("latin-1", errors="ignore") \
            .decode("utf-8", errors="ignore")
