"""Backend adapter: semantic operators over the real JAX serving stack.

Wires `repro.engine.InferenceEngine` (oracle / proxy LLMs served with
continuous batching + single-token predicate scoring) and
`repro.embed.Embedder` into the SemFrame Session — the full production
dataflow of the paper (vLLM + E5 in the original; our TPU-native substrate
here).  Used with randomly-initialized weights in integration tests: the
*plumbing* (prompt construction, log-prob proxy scores, cascade routing,
batched inference) is identical to a trained deployment.
"""
from __future__ import annotations


from repro.configs import ModelConfig, get_smoke
from repro.core.frame import Session
from repro.data.tokenizer import TOKENIZER
from repro.embed.encoder import E5_SMALL, Embedder
from repro.engine.engine import InferenceEngine


class EngineModel:
    """GenerativeModel protocol over an InferenceEngine."""

    def __init__(self, engine: InferenceEngine, *, max_new_tokens: int = 24):
        self.engine = engine
        self.max_new_tokens = max_new_tokens

    def predicate(self, prompts):
        return self.engine.predicate(list(prompts))

    def generate(self, prompts):
        return self.engine.generate(list(prompts), max_new_tokens=self.max_new_tokens)

    def compare(self, prompts):
        return self.engine.compare(list(prompts))

    def choose(self, prompts, n_options):
        return self.engine.choose(list(prompts), n_options)


def make_session(oracle_cfg: ModelConfig | None = None,
                 proxy_cfg: ModelConfig | None = None,
                 embed_cfg: ModelConfig | None = None, *,
                 max_seq: int = 512, seed: int = 0, **session_kw) -> Session:
    """Build a full-JAX Session: oracle + proxy engines + encoder embedder.

    Defaults mirror the paper's pipeline shape at smoke scale: a larger
    oracle (llama-family), a smaller proxy (the Llama-8B/TinyLlama role) and
    a 2-layer encoder; pass published configs to run at real widths.
    """
    oracle_cfg = oracle_cfg or get_smoke("llama3.2-3b").with_(
        vocab_size=TOKENIZER.vocab_size, num_layers=4, d_model=128, d_ff=256)
    proxy_cfg = proxy_cfg or get_smoke("llama3.2-3b").with_(
        vocab_size=TOKENIZER.vocab_size, num_layers=2, d_model=64, d_ff=128)
    oracle = EngineModel(InferenceEngine(oracle_cfg, max_seq=max_seq, seed=seed))
    proxy = EngineModel(InferenceEngine(proxy_cfg, max_seq=max_seq, seed=seed + 1))
    embed_cfg = embed_cfg or E5_SMALL.with_(num_layers=2, d_model=64, num_heads=4,
                                            num_kv_heads=4, d_ff=128)
    embedder = Embedder(embed_cfg, seed=seed + 2)
    return Session(oracle=oracle, proxy=proxy, embedder=embedder, **session_kw)
