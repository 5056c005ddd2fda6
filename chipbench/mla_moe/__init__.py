"""The benchmark's pieces for the latent-attention, sigmoid-routed expert
tree (Kimi K2): its weights, plain reference, check, FLOP count, scopes,
knee sweep and calibration."""
