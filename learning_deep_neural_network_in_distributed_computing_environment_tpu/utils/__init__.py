"""Framework utilities: batch padding and bucketing (`batching`)."""
