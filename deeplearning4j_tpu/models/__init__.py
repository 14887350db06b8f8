"""Native flagship models: `bert` (encoder, TP/SP/PP training),
`causal_lm` (decoder-only LM with cache-aware attention — the generative
serving workload), `seq2seq` (LSTM encoder-decoder with cached greedy
decode), `hybrid_lm` (Mamba-2 / sparse-expert / grouped-query blocks by a
layer pattern, the `nemotron_h` backbone; training path only)."""
