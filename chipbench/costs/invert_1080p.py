"""Operations and bytes one padded batch of invert needs: every byte is
read once and written once; one subtraction per byte."""


def cost(config, batch_size):
    g = config["geometry"]
    n = batch_size * g["height"] * g["width"] * g["channels"]
    return {"flops": float(n), "bytes": float(2 * n)}
