"""ray_tpu.llm.disagg — disaggregated prefill/decode serving.

Splits the two LLM phases into separate replica pools with the KV block
shipped through the runtime's own object plane:

- prefill replicas run BATCHED prefill only (the engine's admission +
  prefill stages, decode stage never dispatched), extract each request's
  KV into a contiguous device buffer (scatter.py) and publish it as an
  OWNED object (handoff.py over core/direct.py put_owned);
- decode replicas borrow the block, scatter it into their slot cache or
  paged pool with ONE fused admission program, and continue fully
  device-resident — speculative decoding included;
- the router (router.py) admits to prefill, tracks handoff refs, binds
  each request to a decode lane, and owns the bounded retry policy for
  dead lanes and lost handoffs.

Serve integration (deployments + builder) lives in ray_tpu.serve.llm
(PrefillServer / DecodeServer / DisaggRouterServer,
build_pd_disagg_deployment). The plain reference (tests/plain_reference.py:
the whole-sequence forward, no cache) is the token-identical oracle: an
N_prefill=1/N_decode=1 deployment emits exactly its tokens
(tests/test_llm_disagg.py).
"""

from ray_tpu.llm.disagg.handoff import (
    HandoffError,
    HandoffLostError,
    decode as decode_handoff,
    encode as encode_handoff,
    fetch as fetch_handoff,
    publish as publish_handoff,
)
from ray_tpu.llm.disagg.router import DisaggRequestError, DisaggRouter

__all__ = [
    "DisaggRequestError",
    "DisaggRouter",
    "HandoffError",
    "HandoffLostError",
    "decode_handoff",
    "encode_handoff",
    "fetch_handoff",
    "publish_handoff",
]
