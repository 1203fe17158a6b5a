"""Quickstart: train a reduced SmolLM on synthetic data with the full
production runner (journal + checkpoint + resume), then serve it.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.launch.serve import serve
from repro.launch.train import train


def main() -> None:
    print("=== train (reduced smollm_360m, 30 steps) ===")
    out = train("smollm_360m", reduced=True, steps=30, batch=8, seq=64,
                ckpt_dir="runs/quickstart", ckpt_every=10)
    print(f"\nloss: {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f} "
          f"({out['wall']:.1f}s)")
    assert out["losses"][-1] < out["losses"][0]

    print("\n=== serve (batched prefill + decode) ===")
    serve("smollm_360m", reduced=True, batch=4, prompt_len=32, gen=8,
          cache_len=64)


if __name__ == "__main__":
    main()
