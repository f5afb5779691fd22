"""`prefill_ms_per_ktok` (see that reader) as the long-document cell reports it: there it moves
`serve_tokens_per_s`, the cell's end-to-end metric, where in the chat cell it moves a latency."""

from benchmark.common import load_reader

read = load_reader("prefill_ms_per_ktok")
