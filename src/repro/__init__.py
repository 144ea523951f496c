"""Reproduction of "Approximate Wireless Communication for Federated Learning"."""
