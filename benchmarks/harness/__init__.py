"""Shared harness code: holds no cell's, configuration's or metric's name."""
