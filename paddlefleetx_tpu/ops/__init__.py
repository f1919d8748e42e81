"""Custom TPU ops: Pallas flash attention, flash-decode (blocked KV-cache)
attention, fused LayerNorm, chunked CE, top-k-prefiltered top-p sampling,
the state-space decode step, the serving prefill's grouped product."""
