"""Model FLOPs of one Whisper train step, from the published shapes.

Forward FLOPs of one segment (a multiply-add counts 2), with d the width,
f the MLP width, T the encoder's frames, S the decoder's tokens and V the
vocabulary:
  encoder, per layer and frame:   8 d^2          q, k, v, out projections
                                  4 T d          scores and their values
                                  4 d f          MLP
  decoder, per layer and token:   8 d^2 + 4 S d  causal self-attention,
                                                 the whole S x S square as
                                                 the program computes it
                                  4 d^2 + 4 T d  cross-attention's q and
                                                 out, scores over T frames
                                  4 d f          MLP
  cross-attention's k and v, per layer and frame: 4 d^2
  the tied head, per token: 2 d V
A train step is 3 x the forward over every segment (backward = 2 x
forward); recomputation under remat is not counted. Norms, biases, the
softmax and the loss are left out, and so is the conv front end, which
the program stubs.
"""


def forward_flops_per_segment(c: dict, frames: int, seq: int) -> float:
    d, f, V = c["d_model"], c["encoder_ffn_dim"], c["vocab_size"]
    T, S = frames, seq
    enc = c["encoder_layers"] * T * (8 * d * d + 4 * T * d + 4 * d * f)
    dec = c["decoder_layers"] * (
        S * (8 * d * d + 4 * S * d + 4 * d * d + 4 * T * d + 4 * d * f)
        + T * 4 * d * d)
    return enc + dec + S * 2 * d * V


def flops_per_step(config: dict, traffic: dict) -> float:
    per = forward_flops_per_segment(config, traffic["frames"],
                                    traffic["seq"])
    return 3.0 * per * traffic["batch"]
