"""The benchmark's own library: process layout, HTTP client, statistics,
Prometheus and trace reduction. Nothing here imports the program."""
