"""Structured output (engine/guided.py + response_format wiring).

Reference surface: response_format json_object/json_schema in
lib/async-openai request types, served via guided-decoding backends.
Tests: the JSON machine's accept/reject behavior, schema-subset
enforcement (properties/required/enum/items/types), mask correctness,
engine-level conformance with a RANDOM tiny model (the point of
constrained decoding: even an untrained model must emit valid documents),
pipelined-engine and HTTP/streaming conformance.
"""

from __future__ import annotations

import json

import pytest

from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
from dynamo_tpu.engine.guided import (
    JsonMachine,
    Reject,
    TokenMasker,
    validate_json_output,
)
from dynamo_tpu.tokenizer import ByteTokenizer

from tests.test_engine import make_req, run_to_completion, tiny_config


def feed(machine: JsonMachine, s: str) -> JsonMachine:
    machine.feed_str(s)
    return machine


# -- machine units -----------------------------------------------------------

@pytest.mark.parametrize("doc", [
    '{"a": 1}', '[1, 2.5, -3e2]', '"hi"', "true", "false", "null", "42",
    '{"a": {"b": [true, null]}, "c": "x"}', "[]", "{}", '[{"k": "v"}]',
    ' { "a" : [ 1 , 2 ] } ', '"esc\\" \\\\ \\n ok"',
])
def test_machine_accepts_valid_json(doc):
    m = feed(JsonMachine(), doc)
    assert m.complete
    json.loads(doc)  # sanity: really valid


@pytest.mark.parametrize("doc", [
    '{"a" 1}', "[1,, 2]", "{,}", "tru ", "nulx", '{"a": }', "[1 2]",
    '{"a": 1} x', "01a", '{"a": 1,}',
])
def test_machine_rejects_invalid_json(doc):
    with pytest.raises(Reject):
        feed(JsonMachine(), doc)


def test_machine_number_termination():
    m = feed(JsonMachine(), "12")
    assert m.complete          # bare int can end at EOS
    m = feed(JsonMachine(), "12.")
    assert not m.complete      # trailing dot is not a number
    m = feed(JsonMachine(), '{"a": 12}')
    assert m.complete


def test_schema_key_membership_and_required():
    schema = {"type": "object",
              "properties": {"name": {"type": "string"},
                             "age": {"type": "number"}},
              "required": ["name"]}
    feed(JsonMachine(schema), '{"name": "x"}')
    feed(JsonMachine(schema), '{"age": 3, "name": "x"}')
    with pytest.raises(Reject):    # unknown key
        feed(JsonMachine(schema), '{"nope": 1}')
    with pytest.raises(Reject):    # required key missing at close
        feed(JsonMachine(schema), '{"age": 3}')
    with pytest.raises(Reject):    # wrong value type for a keyed schema
        feed(JsonMachine(schema), '{"age": "three"')
    with pytest.raises(Reject):    # duplicate key (candidates exclude seen)
        feed(JsonMachine(schema), '{"name": "x", "name"')


def test_schema_enum_and_items():
    schema = {"type": "object",
              "properties": {"mood": {"type": "string",
                                      "enum": ["happy", "sad"]},
                             "tags": {"type": "array",
                                      "items": {"type": "number"}}},
              "required": ["mood"]}
    feed(JsonMachine(schema), '{"mood": "sad", "tags": [1, 2]}')
    with pytest.raises(Reject):
        feed(JsonMachine(schema), '{"mood": "angry"')
    with pytest.raises(Reject):
        feed(JsonMachine(schema), '{"mood": "happy", "tags": ["x"')


def test_schema_root_type():
    with pytest.raises(Reject):
        feed(JsonMachine({"type": "object"}), "[")
    with pytest.raises(Reject):
        feed(JsonMachine({"type": "number"}), '"')
    feed(JsonMachine({"type": "boolean"}), "true")


# -- token masks -------------------------------------------------------------

def _masker(schema=None) -> TokenMasker:
    tok = ByteTokenizer(512)
    pieces = [tok.decode([i]) for i in range(512)]
    return TokenMasker(pieces, [tok.eos_id], schema)


def _allowed_chars(mk: TokenMasker) -> set[str]:
    mask = mk.mask()
    return {mk.pieces[i] for i in range(len(mask))
            if mask[i] and mk.pieces[i]}


def test_mask_start_of_object_schema():
    mk = _masker({"type": "object"})
    allowed = _allowed_chars(mk)
    assert "{" in allowed and "[" not in allowed and "1" not in allowed
    assert not mk.mask()[mk.eos_ids[0]]    # incomplete: EOS blocked


def test_mask_allows_eos_exactly_when_complete():
    mk = _masker()
    for ch in '{"a": 1}':
        mk.advance(ByteTokenizer(512).encode(ch)[0])
    assert mk.complete
    assert mk.mask()[mk.eos_ids[0]]
    assert "," not in _allowed_chars(mk)


def test_mask_key_prefix_constraint():
    mk = _masker({"type": "object", "properties": {"abc": {}, "axe": {}},
                  "required": ["abc"]})
    tok = ByteTokenizer(512)
    for ch in '{"a':
        mk.advance(tok.encode(ch)[0])
    allowed = _allowed_chars(mk)
    assert "b" in allowed and "x" in allowed and "z" not in allowed


# -- engine conformance ------------------------------------------------------

def guided_req(schema, max_tokens=48, rid="g", **kw):
    return make_req(prompt=list(range(40, 52)), max_tokens=max_tokens,
                    rid=rid, guided_json=schema, **kw)


def decode_out(tokens) -> str:
    return ByteTokenizer(512).decode(tokens)


def test_engine_json_object_mode_emits_valid_json():
    core = EngineCore(tiny_config())
    out, fin = run_to_completion(core, [guided_req({})])
    assert fin == {"g"}
    text = decode_out(out["g"])
    validate_json_output(text)  # a RANDOM model emitted parseable JSON


def test_engine_json_schema_mode_conforms():
    # enum-bounded string: a RANDOM model inside a free-form string can
    # burn the whole token budget before closing the quote (see the
    # truncation test below); the enum makes completion certain.
    schema = {"type": "object",
              "properties": {"name": {"type": "string",
                                      "enum": ["ada", "bob"]},
                             "ok": {"type": "boolean"}},
              "required": ["name", "ok"]}
    core = EngineCore(tiny_config())
    out, fin = run_to_completion(core, [guided_req(schema, max_tokens=64)])
    assert fin == {"g"}
    doc = validate_json_output(decode_out(out["g"]), schema)
    assert doc["name"] in ("ada", "bob") and isinstance(doc["ok"], bool)


def test_engine_schema_truncation_on_length_budget():
    """Guided decoding guarantees every PREFIX is grammar-consistent; a
    max_tokens cutoff mid-document finishes with LENGTH and a truncated
    (incomplete but never ill-formed-so-far) body — same contract as the
    reference's guided backends."""
    schema = {"type": "object",
              "properties": {"name": {"type": "string"}},
              "required": ["name"]}
    core = EngineCore(tiny_config())
    out, fin = run_to_completion(core, [guided_req(schema, max_tokens=8)])
    assert fin == {"g"}
    text = decode_out(out["g"])
    # the emitted prefix must itself be machine-consistent
    feed(JsonMachine(schema), text)


def test_guided_and_plain_coexist_in_one_batch():
    """A guided row must not perturb sibling streams: the plain request
    emits exactly what it emits in a guided-free engine."""
    plain_req = lambda: make_req(prompt=list(range(60, 72)),  # noqa: E731
                                 max_tokens=10, rid="p")
    solo, _ = run_to_completion(EngineCore(tiny_config()), [plain_req()])
    both, fin = run_to_completion(EngineCore(tiny_config()), [
        guided_req({}), plain_req()])
    assert fin == {"g", "p"}
    assert both["p"] == solo["p"]
    validate_json_output(decode_out(both["g"]))


def test_guided_sampled_request_conforms():
    """Constrained decoding with temperature>0: sampling happens over the
    masked distribution, output still conforms."""
    schema = {"type": "array", "items": {"type": "number"}}
    core = EngineCore(tiny_config())
    out, fin = run_to_completion(core, [
        guided_req(schema, temperature=0.9, seed=3)])
    assert fin == {"g"}
    doc = validate_json_output(decode_out(out["g"]), schema)
    assert isinstance(doc, list)


async def test_guided_through_pipelined_engine():
    engine = AsyncJaxEngine(EngineCore(tiny_config()))
    toks = []
    async for out in engine.generate(guided_req({}, max_tokens=40)):
        toks.extend(out.token_ids)
    await engine.shutdown()
    validate_json_output(decode_out(toks))


def test_guided_with_spec_decode_enabled():
    """spec_ngram on: guided rows must bypass the verify path and still
    conform (mask semantics are incompatible with multi-token verify)."""
    core = EngineCore(tiny_config(spec_ngram=2, spec_k=4))
    out, fin = run_to_completion(core, [guided_req({})])
    assert fin == {"g"}
    validate_json_output(decode_out(out["g"]))


def test_response_format_preprocessor_mapping():
    from dynamo_tpu.preprocessor.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.protocols.openai import ChatCompletionRequest

    pre = OpenAIPreprocessor("m", ByteTokenizer(512))
    def req(rf):
        return ChatCompletionRequest(
            model="m", messages=[{"role": "user", "content": "hi"}],
            response_format=rf)

    assert pre._sampling(req(None)).guided_json is None
    assert pre._sampling(req({"type": "text"})).guided_json is None
    assert pre._sampling(req({"type": "json_object"})).guided_json == {}
    sch = {"type": "object", "properties": {"a": {}}}
    got = pre._sampling(req({"type": "json_schema",
                             "json_schema": {"name": "x", "schema": sch}}))
    assert got.guided_json == sch


class _SpVocabStub:
    """Minimal HF-tokenizer shape: sentencepiece-style vocab with byte-
    fallback pieces plus an added token that get_vocab() omits."""

    all_special_ids = [0]

    def __init__(self):
        self._vocab = {
            "<s>": 0,          # special → must stay ""
            "▁hello": 1,
            "<0x41>": 2,       # ASCII byte-fallback → "A"
            "<0xE2>": 3,       # non-ASCII UTF-8 fragment → disallowed ""
            "world": 4,
        }                       # id 5 intentionally missing (added token)

    def get_vocab(self):
        return dict(self._vocab)

    def __len__(self):
        return 6

    def convert_ids_to_tokens(self, idx):
        if idx == 5:
            return "▁added"
        inv = {v: k for k, v in self._vocab.items()}
        if idx not in inv:
            raise IndexError(idx)
        return inv[idx]


def test_guided_vocab_sentencepiece_byte_fallback():
    from dynamo_tpu.tokenizer.base import guided_vocab

    class Wrap:
        _tok = _SpVocabStub()

    pieces = guided_vocab(Wrap())
    assert pieces[0] == ""          # special token never matchable
    assert pieces[1] == " hello"    # ▁ marker → leading space
    assert pieces[2] == "A"         # <0x41> byte-fallback → its character
    assert pieces[3] == ""          # lone non-ASCII byte stays disallowed
    assert pieces[4] == "world"
    assert pieces[5] == " added"    # backfilled via convert_ids_to_tokens


@pytest.mark.slow
def test_guided_in_a_mixed_step_matches_solo():
    """Guided rows join the mixed launch via per-row masks: the guided
    stream AND its plain sibling (whose multi-chunk prompt forces real
    mixed steps while the guided row decodes) are the streams the two
    requests emit one at a time, where no step mixes phases."""
    schema = {"type": "object",
              "properties": {"name": {"type": "string",
                                      "enum": ["ada", "bob"]},
                             "ok": {"type": "boolean"}},
              "required": ["name", "ok"]}

    def reqs():
        return [guided_req(schema, max_tokens=64),
                make_req(prompt=[(3 * j) % 90 for j in range(40)],
                         max_tokens=10, rid="p")]

    uni, fin = run_to_completion(EngineCore(tiny_config()), reqs())
    assert fin == {"g", "p"}
    solo_core, solo = EngineCore(tiny_config()), {}
    for r in reqs():
        solo.update(run_to_completion(solo_core, [r])[0])
    assert uni == solo
    validate_json_output(decode_out(uni["g"]), schema)
