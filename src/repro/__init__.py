"""repro: TPU reproduction of "UCCL-Zip: Lossless Compression Supercharged
GPU Communication" on the jax/Pallas stack."""
