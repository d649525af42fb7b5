"""The bodies pool: a patched body decodes, through the program's own
receiver, to the same spans under the new trace ids."""

import numpy as np

import corpus
import wire
from tempo_tpu.receivers import otlp


def test_patched_body_decodes_to_same_spans_under_new_ids():
    block = corpus.make_block(24, 16, [7, 0, 0], 1_700_000_000 * 10**9)
    body = wire.PatchableBody(corpus.encode_push(block))
    assert body.n_traces == 24 and body.n_spans == 24 * 16
    assert set(body.ids) == {bytes.fromhex(h) for h in corpus.trace_hex(block)}
    new = np.random.default_rng(1).integers(0, 256, (24, 16), dtype=np.uint8)
    before = otlp.decode_traces_request(body.patched(np.frombuffer(
        b"".join(body.ids), np.uint8).reshape(24, 16)))
    after = otlp.decode_traces_request(body.patched(new))

    def spans_by_trace(traces):
        return {t.trace_id: sorted((s.span_id, s.name, s.start_unix_nano, s.duration_nano)
                                   for _, spans in t.batches for s in spans) for t in traces}

    old, got = spans_by_trace(before), spans_by_trace(after)
    assert set(got) == {new[k].tobytes() for k in range(24)}
    for k, tid in enumerate(body.ids):
        assert got[new[k].tobytes()] == old[tid]
        assert {s[0] for s in old[tid]} == body.span_sets[k]
    # the pool's own body is untouched by a send
    assert wire.trace_id_offsets(body.buf.tobytes())[2] == body.ids


def test_span_ids_reads_what_the_encoder_wrote():
    block = corpus.make_block(3, 16, [8, 0, 0], 1_700_000_000 * 10**9)
    want = {r.astype(">u4").tobytes() for r in block.cols["span_id"]}
    assert wire.span_ids(corpus.encode_push(block)) == want
