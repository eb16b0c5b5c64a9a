"""Client-faithful SGLang cold-start (VERDICT r4 missing #1).

Reproduces the wire sequence SGLang's DefaultModelLoader performs when
cold-starting from the HF Hub through ``HTTPS_PROXY``
(`/root/reference/README.md:21` names SGLang in the client matrix).
Unlike the vLLM stand-in (`tests/vllm_load_client.py`, hf_transfer-shaped
parallel ranged GETs), SGLang's default load path is:

1. ``AutoConfig``-shaped metadata: ``GET /api/models/{repo}`` +
   ``config.json`` via resolve;
2. the REAL ``huggingface_hub.snapshot_download`` — the exact library
   call SGLang's loader makes — with SGLang's weight patterns
   (``*.safetensors`` / ``*.bin`` / ``*.pt``) and index files: per-file
   metadata HEAD (stops at the CDN 302, reads ``X-Linked-Etag``), then a
   sequential single-stream GET per file (no hf_transfer);
3. ``safetensors.safe_open``-style per-tensor reads off the downloaded
   shards, each ``device_put`` — the load ends in device memory like
   SGLang's weight iterator.

Proxying comes entirely from the environment (HTTPS_PROXY +
REQUESTS_CA_BUNDLE), as with the real engine.

Usage: sglang_load_client.py <endpoint> <model> <dest>
Prints one JSON line with timings/bytes/fingerprints.
"""

import json
import os
import sys
import time
from pathlib import Path

import requests

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SGLANG_WEIGHT_PATTERNS = ["*.safetensors", "*.bin", "*.pt"]
SGLANG_AUX_PATTERNS = ["*.json", "*.txt", "tokenizer*"]


def main() -> int:
    endpoint, model, dest = sys.argv[1], sys.argv[2], Path(sys.argv[3])
    t0 = time.time()

    sess = requests.Session()
    # step 1: AutoConfig-shaped metadata (transformers does this before
    # the loader runs)
    api = sess.get(f"{endpoint}/api/models/{model}/revision/main",
                   timeout=60)
    api.raise_for_status()
    cfg = sess.get(f"{endpoint}/{model}/resolve/main/config.json",
                   timeout=60)
    cfg.raise_for_status()

    # step 2: the real library call SGLang makes
    from huggingface_hub import snapshot_download

    snap = snapshot_download(
        model,
        allow_patterns=SGLANG_WEIGHT_PATTERNS + SGLANG_AUX_PATTERNS,
        ignore_patterns=["original/**/*"],  # SGLang's default ignore
        local_dir=dest,
    )
    dl_secs = time.time() - t0

    # step 3: safe_open-per-tensor reads → device (SGLang's weight
    # iterator yields (name, tensor) pairs shard by shard)
    import numpy as np
    from safetensors import safe_open

    import jax

    # a client-fidelity test, not a device test: stay off any chip
    jax.config.update("jax_platforms", "cpu")

    fps = {}
    nbytes = 0
    t1 = time.time()
    for shard in sorted(Path(snap).glob("*.safetensors")):
        with safe_open(str(shard), framework="np") as f:
            for name in f.keys():
                arr = f.get_tensor(name)
                dev = jax.device_put(arr)
                dev.block_until_ready()
                nbytes += arr.nbytes
                fps[name] = [float(np.asarray(dev).sum()),
                             float(np.abs(np.asarray(dev)).sum())]
    load_secs = time.time() - t1

    print(json.dumps({
        "client": "sglang",
        "download_secs": round(dl_secs, 3),
        "load_secs": round(load_secs, 3),
        "weight_bytes": nbytes,
        "tensors": len(fps),
        "fp": fps,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
