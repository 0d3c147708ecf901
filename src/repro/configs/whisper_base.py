"""Whisper-base — encoder-decoder audio model [arXiv:2212.04356],
https://huggingface.co/openai/whisper-base (config.json).

As published: 6 encoder and 6 decoder layers, d_model 512, 8 heads of 64,
FFN 2048, vocabulary 51,865; pre-LayerNorm with bias, exact (erf) GELU;
biases on q, v, out and both MLP matrices and none on k; fixed sinusoidal
positions over the encoder's 1,500 frames and a learned table of the
decoder's 448 positions; the token embedding tied to the output.

Departures that remain:
  * the mel-spectrogram and conv front end (two Conv1d over 80 mel bins,
    the second with stride 2; about 2% of the forward FLOPs) is a stub:
    the batch carries precomputed frame embeddings (B, 1500, 512);
  * bf16 parameters and compute (the published checkpoint is float32).
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="whisper-base", arch_type="audio",
    n_layers=6, encoder_layers=6, d_model=512, vocab=51865,
    n_heads=8, n_kv_heads=8, d_head=64,
    d_ff=2048, mlp="gelu", norm="layernorm", use_rope=False,
    tie_embeddings=True, bias=True, max_target_positions=448,
    frontend="audio_stub", frontend_seq=1500,
)

SMOKE = ModelConfig(
    name="whisper-smoke", arch_type="audio",
    n_layers=2, encoder_layers=2, d_model=96, vocab=512,
    n_heads=4, n_kv_heads=4, d_head=24,
    d_ff=192, mlp="gelu", norm="layernorm", use_rope=False,
    tie_embeddings=True, bias=True, max_target_positions=448,
    frontend="audio_stub", frontend_seq=24, dtype="float32",
)
